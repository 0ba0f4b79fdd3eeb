"""State-space recurrence kernels for the serving path: the selective scan of
Mamba-1 and the matrix-state recurrence of Mamba-2 (SSD).

**Mamba-1** (``ssm_*``). One layer, per token ``t`` and channel ``e`` (``N``
state values a channel)::

    h_t[n, e] = exp(dt_t[e] * A[n, e]) * h_{t-1}[n, e] + dt_t[e] * x_t[e] * B_t[n]
    y_t[e]    = sum_n h_t[n, e] * C_t[n]

all in float32. It is vector-unit work with no matrix product in it, and what
bounds it is the state: ``[N, E]`` float32 per sequence and layer (320 KiB at
``E`` 5120, ``N`` 16). The layout puts ``E`` on the lanes and ``N`` on the
sublanes — ``[.., N, E]`` tiles with no padding; ``[.., E, N]`` would pad 16
lanes to 128, eight times the bytes — and ``B_t``/``C_t`` reach the kernels
already spread over 128 lanes (``[T, N, 128]``), so that a kernel only
repeats whole registers.

**Mamba-2** (``ssd_*``). The state is a matrix per head, ``S[h]`` ``[P, N]``
(``H`` heads of ``P`` channels, ``E = H * P``), the decay one scalar a head
and ``B_t``/``C_t`` shared by the heads of a group (``G`` groups of ``H / G``
heads: head ``h`` reads ``B_t[h // (H / G)]``; granite has one, nemotron_h
eight)::

    S_t[h] = exp(dt_t[h] * a[h]) * S_{t-1}[h] + dt_t[h] * x_t[h] (outer) B_t
    y_t[h] = S_t[h] C_t

It is the recurrence above with ``dt`` and ``A`` constant over a head's
channels, so the state takes the SAME layout, ``[.., N, E]`` with channel
``e = h * P + p`` on the lanes: 4 MiB a sequence a layer at ``N`` 128, ``E``
8192 (128 heads of 64), no padding, two heads of 64 a 128-lane tile. What
differs is how it is computed. A decode row is bound by its state's bytes
(one read and one write of 4 MiB), so :func:`ssd_decode_step` moves it in
blocks of channels with the decay made once a channel (not once a state
value: no ``exp`` over ``[N, E]``). Prompt rows take the *product form*
(:func:`ssd_chunk_scan`): over a chunk of ``Q`` tokens the intra-chunk part
``(L o C B^T) X``, the carried part ``C S`` and the chunk's new state ``B^T
(decay * X)`` are matrix products on the MXU, where Mamba-1's scan is a loop
over tokens on the vector unit.

- :func:`ssm_decode_step` / :func:`ssd_decode_step`: one token per row, each
  row with a state of its own somewhere in the pool ``[Lm, slots, N, E]``.
  The pool is aliased through the call; a grid step reads a row's state block
  where it lies (the layer and the slot come from prefetched scalars),
  updates it and writes it back: one read and one write of each state,
  nothing else of the pool touched. The row's convolution tail rides along:
  its block of the tail pool ``[Lm, slots, (K-1)*8, W/8]`` (``W`` the
  convolved channels padded to whole tiles: ``E`` for Mamba-1, ``E + 2 G N``
  for Mamba-2, where x, B and C are convolved together) comes in, drops its
  oldest tap, takes the row's new input as its newest and goes back. Left to
  XLA, that update was a row scatter of 30 KiB rows, which costs by the row:
  3.3 ms of a 19 ms decode step at 128 rows x 26 layers (my chip run, PR 31),
  against nothing here (the blocks move under the state's).
- :func:`ssm_chunk_scan` / :func:`ssd_chunk_scan`: a pass's packed prompt
  rows, ``G`` chunk slots of ``Cs`` rows. The state stays in on-chip memory
  across a slot's token blocks; at a slot's first block it is loaded from
  ``h0`` unless the slot continues the one before it (``cont``); after a
  slot's last block it is written to ``hT``. Rows with ``dt = 0`` leave the
  state as it is (``exp(0) = 1``, nothing added), which is how a chunk
  shorter than its slot is padded.

Each has a plain-XLA form (``*_xla``) for shapes the kernel refuses (``E`` not
a multiple of 128, a slot size not a multiple of 8) and as what the tests
hold the kernels to; the Mamba-2 forms are the Mamba-1 forms with ``dt`` and
``a`` repeated over a head's channels, token by token. On the CPU the kernels
run through the Pallas interpreter (``_backend.interpret()``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import _backend


LANES = 128


def lane_spread(v: jax.Array) -> jax.Array:
    """``[T, N]`` -> ``[T, N, 128]`` float32, each value on all 128 lanes."""
    return jnp.broadcast_to(v.astype(jnp.float32)[..., None],
                            v.shape + (LANES,))


def _channel_block(E: int) -> int:
    for eb in (512, 256, 128):
        if E % eb == 0:
            return eb
    return 0


# --------------------------------------------------------------------------- #
# one token per row, state in the pool
# --------------------------------------------------------------------------- #

TAP_ROWS = 8      # sublane rows one tap of a convolution tail takes


def _decode_kernel(l_ref, slot_ref, dt_ref, x_ref, b_ref, c_ref, a_ref,
                   new_ref, h_ref, t_ref, y_ref, ho_ref, to_ref):
    del l_ref, slot_ref               # read by the index maps
    dt = dt_ref[0]                                            # [1, E]
    reps = a_ref.shape[1] // LANES
    h = (jnp.exp(dt * a_ref[...]) * h_ref[0, 0]
         + (dt * x_ref[0]) * pltpu.repeat(b_ref[0], reps, axis=1))
    ho_ref[0, 0] = h
    y_ref[0] = jnp.sum(h * pltpu.repeat(c_ref[0], reps, axis=1), axis=0,
                       keepdims=True)
    # the tail: taps 1.. move down one, the row's new input is the newest
    kept = t_ref.shape[2] - TAP_ROWS
    if kept:
        to_ref[0, 0, :kept] = t_ref[0, 0, TAP_ROWS:]
    to_ref[0, 0, kept:] = new_ref[0]


def ssm_decode_step(pool: jax.Array, tails: jax.Array, l, slots: jax.Array,
                    dt: jax.Array, x: jax.Array, B: jax.Array, C: jax.Array,
                    A: jax.Array, new: jax.Array):
    """One recurrence step for ``S`` rows whose states lie in ``pool``, and
    the shift of their convolution tails in ``tails``.

    pool:  [Lm, NS, N, E] float32 — ALIASED: the returned pool reuses it
    tails: [Lm, NS, (K-1)*8, E/8] float32 — ALIASED: tap ``j`` of a slot is
           its rows ``8j..8j+7``, channel ``e`` at ``[e // (E/8), e % (E/8)]``
    l:     int32 scalar, the layer of the pools this call updates
    slots: [S] int32, each row's slot (rows that share a slot — the engine's
           padding rows, all at its dump slot — leave any one's state there)
    dt, x: [S, E] float32     B, C: [S, N] float32     A: [N, E] float32
    new:   [S, E] the convolution's input at this token: each row's tail
           drops its oldest tap and takes this as its newest

    Returns ``(y [S, E] float32, pool, tails)``."""
    Lm, NS, N, E = pool.shape
    S = slots.shape[0]
    TR, E8 = tails.shape[2:]
    if E8 % LANES or N % 8:
        return ssm_decode_step_xla(pool, tails, l, slots, dt, x, B, C, A, new)
    row = lambda i, l_ref, s_ref: (i, 0, 0)
    slot = lambda i, l_ref, s_ref: (l_ref[0], s_ref[i], 0, 0)
    state = pl.BlockSpec((1, 1, N, E), slot)
    tail = pl.BlockSpec((1, 1, TR, E8), slot)
    call = pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(S,),
            in_specs=[pl.BlockSpec((1, 1, E), row), pl.BlockSpec((1, 1, E), row),
                      pl.BlockSpec((1, N, LANES), row),
                      pl.BlockSpec((1, N, LANES), row),
                      pl.BlockSpec((N, E), lambda i, l_ref, s_ref: (0, 0)),
                      pl.BlockSpec((1, TAP_ROWS, E8), row), state, tail],
            out_specs=[pl.BlockSpec((1, 1, E), row), state, tail]),
        out_shape=[jax.ShapeDtypeStruct((S, 1, E), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct(tails.shape, tails.dtype)],
        input_output_aliases={8: 1, 9: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_backend.interpret(),
    )
    with jax.named_scope("ssm_decode_step"):
        y, pool, tails = call(
            jnp.asarray(l, jnp.int32).reshape(1), slots.astype(jnp.int32),
            dt.astype(jnp.float32)[:, None], x.astype(jnp.float32)[:, None],
            lane_spread(B), lane_spread(C), A.astype(jnp.float32),
            new.astype(tails.dtype).reshape(S, TAP_ROWS, E8), pool, tails)
    return y[:, 0], pool, tails


def ssm_decode_step_xla(pool, tails, l, slots, dt, x, B, C, A, new):
    """:func:`ssm_decode_step` in plain XLA: gather the rows' states and
    tails, update, scatter them back (in place where the pools are a donated
    carry)."""
    Lm, NS, N, E = pool.shape
    TR, E8 = tails.shape[2:]
    with jax.named_scope("ssm_decode_step_xla"):
        flat = pool.reshape(Lm * NS, N, E)
        rows = l * NS + slots
        dt = dt.astype(jnp.float32)
        h = (jnp.exp(dt[:, None, :] * A[None]) * flat[rows]
             + (dt * x)[:, None, :] * B.astype(jnp.float32)[:, :, None])
        y = jnp.sum(h * C.astype(jnp.float32)[:, :, None], axis=1)
        tflat = tails.reshape(Lm * NS, TR, E8)
        shifted = jnp.concatenate(
            [tflat[rows][:, TAP_ROWS:],
             new.astype(tails.dtype).reshape(-1, TAP_ROWS, E8)], axis=1)
        return (y, flat.at[rows].set(h).reshape(pool.shape),
                tflat.at[rows].set(shifted).reshape(tails.shape))


# --------------------------------------------------------------------------- #
# a pass's packed prompt rows, state on chip across a slot
# --------------------------------------------------------------------------- #

def _scan_kernel(cont_ref, dt_ref, x_ref, b_ref, c_ref, a_ref, h0_ref,
                 y_ref, ht_ref, h_sc, *, blocks_per_slot: int, rows: int):
    tb, e = pl.program_id(0), pl.program_id(1)
    g = tb // blocks_per_slot

    @pl.when(jnp.logical_and(tb % blocks_per_slot == 0, cont_ref[g] == 0))
    def _():
        h_sc[e] = h0_ref[0]

    A = a_ref[...]                                            # [N, Eb]
    reps = A.shape[1] // LANES

    def body(i, h):
        t8 = pl.multiple_of(i * 8, 8)
        dt8 = dt_ref[pl.ds(t8, 8), :]                         # [8, Eb]
        dx8 = dt8 * x_ref[pl.ds(t8, 8), :]
        b8 = b_ref[pl.ds(t8, 8)]                              # [8, N, 128]
        c8 = c_ref[pl.ds(t8, 8)]
        ys = []
        for k in range(8):
            h = (jnp.exp(dt8[k:k + 1] * A) * h
                 + dx8[k:k + 1] * pltpu.repeat(b8[k], reps, axis=1))
            ys.append(jnp.sum(h * pltpu.repeat(c8[k], reps, axis=1), axis=0,
                              keepdims=True))
        y_ref[pl.ds(t8, 8), :] = jnp.concatenate(ys, axis=0)
        return h

    h = jax.lax.fori_loop(0, rows // 8, body, h_sc[e])
    h_sc[e] = h
    ht_ref[0] = h


def ssm_chunk_scan(dt: jax.Array, x: jax.Array, B: jax.Array, C: jax.Array,
                   A: jax.Array, h0: jax.Array, cont: jax.Array):
    """The recurrence over ``G`` chunk slots of ``Cs`` packed rows each.

    dt, x: [G*Cs, E] float32 (``dt`` zero on rows that hold no token)
    B, C:  [G*Cs, N] float32     A: [N, E] float32
    h0:    [G, N, E] float32, the state a slot starts from
    cont:  [G] int32, non-zero where a slot takes over the state the slot
           before it ended with (same sequence, next ``Cs`` tokens) and
           ignores ``h0``; slot 0 never does

    Returns ``(y [G*Cs, E] float32, hT [G, N, E] float32)``: ``hT[g]`` is the
    state after slot ``g``'s last row."""
    G, N, E = h0.shape
    T = dt.shape[0]
    Cs = T // G
    Eb = _channel_block(E)
    if not Eb or Cs % 8 or N % 8:
        return ssm_chunk_scan_xla(dt, x, B, C, A, h0, cont)
    TB = next(t for t in (128, 64, 32, 16, 8) if Cs % t == 0)
    bps = Cs // TB
    cont = cont.astype(jnp.int32).at[0].set(0)
    tok = lambda tb, e, c: (tb, e)
    spread = lambda tb, e, c: (tb, 0, 0)
    slot = lambda tb, e, c: (tb // bps, 0, e)
    call = pl.pallas_call(
        functools.partial(_scan_kernel, blocks_per_slot=bps, rows=TB),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(T // TB, E // Eb),
            in_specs=[pl.BlockSpec((TB, Eb), tok), pl.BlockSpec((TB, Eb), tok),
                      pl.BlockSpec((TB, N, LANES), spread),
                      pl.BlockSpec((TB, N, LANES), spread),
                      pl.BlockSpec((N, Eb), lambda tb, e, c: (0, e)),
                      pl.BlockSpec((1, N, Eb), slot)],
            out_specs=[pl.BlockSpec((TB, Eb), tok),
                       pl.BlockSpec((1, N, Eb), slot)],
            scratch_shapes=[pltpu.VMEM((E // Eb, N, Eb), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((T, E), jnp.float32),
                   jax.ShapeDtypeStruct((G, N, E), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_backend.interpret(),
    )
    with jax.named_scope("ssm_chunk_scan"):
        y, hT = call(cont, dt.astype(jnp.float32), x.astype(jnp.float32),
                     lane_spread(B), lane_spread(C), A.astype(jnp.float32),
                     h0.astype(jnp.float32))
    return y, hT


def ssm_chunk_scan_xla(dt, x, B, C, A, h0, cont):
    """:func:`ssm_chunk_scan` in plain XLA: token by token, slot after slot."""
    G, N, E = h0.shape
    Cs = dt.shape[0] // G
    f32 = lambda v: v.astype(jnp.float32).reshape((G, Cs) + v.shape[1:])
    dt, x, B, C = f32(dt), f32(x), f32(B), f32(C)

    def step(h, row):
        dt_t, x_t, b_t, c_t = row
        h = (jnp.exp(dt_t[None, :] * A) * h
             + (dt_t * x_t)[None, :] * b_t[:, None])
        return h, jnp.sum(h * c_t[:, None], axis=0)

    with jax.named_scope("ssm_chunk_scan_xla"):
        ys, hs = [], []
        h = jnp.zeros((N, E), jnp.float32)
        for g in range(G):
            start = h0[g].astype(jnp.float32)
            h = jnp.where(cont[g] != 0, h, start) if g else start
            h, y = jax.lax.scan(step, h, (dt[g], x[g], B[g], C[g]))
            ys.append(y)
            hs.append(h)
        return jnp.concatenate(ys), jnp.stack(hs)


# --------------------------------------------------------------------------- #
# Mamba-2 (SSD): a matrix state per head, in the same [N, E] layout
# --------------------------------------------------------------------------- #

_HIGHEST = jax.lax.Precision.HIGHEST


def _per_channel(v: jax.Array, P: int) -> jax.Array:
    """``[.., H]`` -> ``[.., H * P]``: a head's value on each of its channels."""
    return jnp.repeat(v.astype(jnp.float32), P, axis=-1)


def _tail_rows(new: jax.Array, tails: jax.Array) -> jax.Array:
    """A row's new convolution input ``[S, W]`` as the tail pool holds a tap:
    ``[S, 8, W8]``, zero past ``W`` (the pool pads the convolved channels to
    whole tiles)."""
    W8 = tails.shape[-1]
    new = new.astype(tails.dtype)
    return jnp.pad(new, ((0, 0), (0, TAP_ROWS * W8 - new.shape[1]))).reshape(
        -1, TAP_ROWS, W8)


def _ssd_decode_kernel(l_ref, slot_ref, da_ref, dx_ref, b_ref, c_ref, new_ref,
                       h_ref, t_ref, y_ref, ho_ref, to_ref, *, groups=None):
    del l_ref, slot_ref               # read by the index maps
    if groups is None:
        # one group: B and C arrive spread over 128 lanes, every channel's
        reps = h_ref.shape[3] // LANES
        h = (da_ref[0] * h_ref[0, 0]
             + dx_ref[0] * pltpu.repeat(b_ref[0], reps, axis=1))
        ho_ref[0, 0] = h.astype(ho_ref.dtype)
        y_ref[0] = jnp.sum(h * pltpu.repeat(c_ref[0], reps, axis=1), axis=0,
                           keepdims=True)
    else:
        # G groups: B and C arrive as they are, [G, N] with N on the lanes
        # (spread over 128 lanes in HBM they were 64 MiB each at 128 rows x 8
        # groups, a quarter of the step's traffic). A group's N values are
        # turned down the sublanes here — the diagonal of its row repeated N
        # times, summed along the lanes — and multiply their channels by
        # broadcast
        held, span = groups     # groups a channel block holds / blocks a group
        N = h_ref.shape[2]
        Eg = h_ref.shape[3] // held
        first = pl.program_id(1) * held if span == 1 \
            else pl.program_id(1) // span
        diag = (jax.lax.broadcasted_iota(jnp.int32, (N, N), 0)
                == jax.lax.broadcasted_iota(jnp.int32, (N, N), 1))

        def column(ref, g):                                   # -> [N, 1]
            row = ref[0, pl.ds(first + g, 1), :]              # [1, N]
            return jnp.sum(jnp.where(diag, jnp.broadcast_to(row, (N, N)),
                                     0.0), axis=1, keepdims=True)

        for g in range(held):
            lanes = slice(g * Eg, (g + 1) * Eg)
            h = (da_ref[0, :, lanes] * h_ref[0, 0, :, lanes]
                 + dx_ref[0, :, lanes] * column(b_ref, g))
            ho_ref[0, 0, :, lanes] = h.astype(ho_ref.dtype)
            y_ref[0, :, lanes] = jnp.sum(h * column(c_ref, g), axis=0,
                                         keepdims=True)

    # the tail's block is the row's, whatever the channel block: shifted once
    @pl.when(pl.program_id(1) == 0)
    def _():
        kept = t_ref.shape[2] - TAP_ROWS
        if kept:
            to_ref[0, 0, :kept] = t_ref[0, 0, TAP_ROWS:]
        to_ref[0, 0, kept:] = new_ref[0]


def _decode_block(E: int) -> int:
    for eb in (2048, 1024, 512, 256, 128):
        if E % eb == 0:
            return eb
    return 0


def _group_blocks(Eb: int, Eg: int):
    """A channel block of ``Eb`` against groups of ``Eg`` channels: ``(groups
    a block holds, channel blocks a group spans)`` — one of them is 1 — or
    None where neither divides the other or a group is not whole lane
    tiles."""
    if Eg % LANES:
        return None
    if Eb % Eg == 0:
        return Eb // Eg, 1
    return (1, Eg // Eb) if Eg % Eb == 0 else None


def ssd_decode_step(pool: jax.Array, tails: jax.Array, l, slots: jax.Array,
                    dt: jax.Array, x: jax.Array, B: jax.Array, C: jax.Array,
                    a: jax.Array, new: jax.Array):
    """One step of the Mamba-2 recurrence for ``S`` rows whose states lie in
    ``pool``, and the shift of their convolution tails in ``tails``.

    pool:  [Lm, NS, N, E] float32 — ALIASED; channel ``h * P + p`` of head
           ``h`` on the lanes (the module's docstring)
    tails: [Lm, NS, (K-1)*8, W8] float32 — ALIASED: tap ``j`` of a slot is
           its rows ``8j..8j+7``, convolved channel ``w`` at ``[w // W8,
           w % W8]``
    l, slots: as :func:`ssm_decode_step`
    dt:    [S, H] float32     x: [S, E]     a: [H] (negative)
    B, C:  [S, N] (one group: handed to the kernel spread over 128 lanes),
           or [S, G, N]: head ``h`` reads group ``h // (H / G)``, channels
           ``g E / G .. (g + 1) E / G - 1`` group g (handed over as they
           are: the kernel turns a group's N values down the sublanes
           itself)
    new:   [S, W] the convolution's input at this token (x, B and C's)

    Returns ``(y [S, E] float32, pool, tails)``. Per row the kernel reads and
    writes the state once, in blocks of channels: 2 x 4 MiB at ``N`` 128,
    ``E`` 8192, which is its floor."""
    Lm, NS, N, E = pool.shape
    S, H = dt.shape
    TR, W8 = tails.shape[2:]
    Eb = _decode_block(E)
    G = B.shape[1] if B.ndim == 3 else 1
    gb = _group_blocks(Eb, E // G) if Eb and G > 1 else None
    if not Eb or W8 % LANES or N % 8 or (G > 1 and gb is None):
        return ssd_decode_step_xla(pool, tails, l, slots, dt, x, B, C, a, new)
    P = E // H
    with jax.named_scope("ssd_decode_step"):
        dt = dt.astype(jnp.float32)
        da = _per_channel(jnp.exp(dt * a.astype(jnp.float32)), P)
        dx = _per_channel(dt, P) * x.astype(jnp.float32)
        row = lambda i, e, l_ref, s_ref: (i, 0, 0)
        chan = lambda i, e, l_ref, s_ref: (i, 0, e)
        state = pl.BlockSpec(
            (1, 1, N, Eb), lambda i, e, l_ref, s_ref: (l_ref[0], s_ref[i], 0, e))
        tail = pl.BlockSpec(
            (1, 1, TR, W8), lambda i, e, l_ref, s_ref: (l_ref[0], s_ref[i], 0, 0))
        if G == 1:
            kernel, spread = _ssd_decode_kernel, lane_spread
            group = pl.BlockSpec((1, N, LANES), row)
        else:       # [S, G, N] whole a row: the kernel picks its block's
            kernel = functools.partial(_ssd_decode_kernel, groups=gb)
            spread = lambda v: v.astype(jnp.float32)
            group = pl.BlockSpec((1, G, N), row)
        call = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(S, E // Eb),
                in_specs=[pl.BlockSpec((1, 1, Eb), chan),
                          pl.BlockSpec((1, 1, Eb), chan), group, group,
                          pl.BlockSpec((1, TAP_ROWS, W8), row), state, tail],
                out_specs=[pl.BlockSpec((1, 1, Eb), chan), state, tail]),
            out_shape=[jax.ShapeDtypeStruct((S, 1, E), jnp.float32),
                       jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                       jax.ShapeDtypeStruct(tails.shape, tails.dtype)],
            input_output_aliases={7: 1, 8: 2},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=_backend.interpret(),
        )
        y, pool, tails = call(
            jnp.asarray(l, jnp.int32).reshape(1), slots.astype(jnp.int32),
            da[:, None], dx[:, None], spread(B), spread(C),
            _tail_rows(new, tails), pool, tails)
    return y[:, 0], pool, tails


def _per_group_channel(v: jax.Array, E: int) -> jax.Array:
    """``[.., G, N]`` -> ``[.., N, E]``: a group's values on each of its
    ``E / G`` channels."""
    G = v.shape[-2]
    return jnp.repeat(jnp.swapaxes(v.astype(jnp.float32), -1, -2), E // G,
                      axis=-1)


def ssd_decode_step_xla(pool, tails, l, slots, dt, x, B, C, a, new):
    """:func:`ssd_decode_step` in plain XLA: the Mamba-1 form with ``dt`` and
    ``a`` repeated over each head's channels (and, with more than one group,
    ``B`` and ``C`` over each group's)."""
    Lm, NS, N, E = pool.shape
    P = E // dt.shape[1]
    if B.ndim == 3:
        TR, W8 = tails.shape[2:]
        with jax.named_scope("ssd_decode_step_xla"):
            flat = pool.reshape(Lm * NS, N, E)
            rows = l * NS + slots
            dt_c = _per_channel(dt, P)
            h = (jnp.exp(dt_c * _per_channel(a, P))[:, None, :] * flat[rows]
                 + (dt_c * x.astype(jnp.float32))[:, None, :]
                 * _per_group_channel(B, E))
            y = jnp.sum(h * _per_group_channel(C, E), axis=1)
            tflat = tails.reshape(Lm * NS, TR, W8)
            shifted = jnp.concatenate(
                [tflat[rows][:, TAP_ROWS:], _tail_rows(new, tails)], axis=1)
            return (y, flat.at[rows].set(h).reshape(pool.shape),
                    tflat.at[rows].set(shifted).reshape(tails.shape))
    with jax.named_scope("ssd_decode_step_xla"):
        A = jnp.broadcast_to(_per_channel(a, P)[None, :], (N, E))
        return ssm_decode_step_xla(pool, tails, l, slots, _per_channel(dt, P),
                                   x.astype(jnp.float32), B, C, A,
                                   _tail_rows(new, tails))


def _ssd_scan_kernel(cont_ref, x_ref, bt_ref, c_ref, col_ref, row_ref, h0_ref,
                     y_ref, ht_ref, h_sc, g_sc, *, blocks_per_slot: int,
                     P: int, blocks_per_group: int = 0):
    tb, e = pl.program_id(0), pl.program_id(1)
    g = tb // blocks_per_slot
    f32 = jnp.float32
    dot = lambda a, b: jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=f32)

    @pl.when(jnp.logical_and(tb % blocks_per_slot == 0, cont_ref[g] == 0))
    def _():
        h_sc[e] = h0_ref[0]

    if not blocks_per_group:
        Cm, Bt = c_ref[...], bt_ref[0]                   # [Q, N], [N, Q]
        first_of_group = e == 0     # C B^T: one group, every head's
    else:       # the group of channel block e; its blocks follow one another
        Cm, Bt = c_ref[0], bt_ref[0, 0]
        first_of_group = e % blocks_per_group == 0

    @pl.when(first_of_group)
    def _():
        g_sc[...] = dot(Cm, Bt)

    Q, Eb = x_ref.shape
    hpt = LANES // P                                     # heads a lane tile
    G = g_sc[...]
    col, row = col_ref[0, 0], row_ref[0, 0]              # [Q, Hb], [Hb, Q]
    seen = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1) // P
    S_all = h_sc[e]                                      # [N, Eb]
    ys, states = [], []
    for k in range(Eb // LANES):
        X = x_ref[:, k * LANES:(k + 1) * LANES]          # dt * x  [Q, 128]
        S = S_all[:, k * LANES:(k + 1) * LANES]          # [N, 128]
        y = jnp.zeros((Q, LANES), f32)
        carried = jnp.zeros((Q, LANES), f32)   # exp(cum_t), by the lane's head
        to_end = jnp.zeros((Q, LANES), f32)    # exp(cum_Q - cum_t)
        whole = jnp.zeros((1, LANES), f32)     # exp(cum_Q)
        for i in range(hpt):
            j = k * hpt + i
            cj, rj = col[:, j:j + 1], row[j:j + 1, :]    # [Q, 1], [1, Q]
            last = cj[Q - 1:Q, :]                        # [1, 1]
            L = jnp.exp(jnp.where(seen, cj - rj, -jnp.inf))
            on = lane_head == i
            y = y + dot(L * G, jnp.where(on, X, 0.0))
            carried = jnp.where(on, jnp.exp(cj), carried)
            to_end = jnp.where(on, jnp.exp(last - cj), to_end)
            whole = jnp.where(on, jnp.exp(last), whole)
        ys.append(y + carried * dot(Cm, S))
        states.append(whole * S + dot(Bt, X * to_end))
    y_ref[...] = ys[0] if len(ys) == 1 else jnp.concatenate(ys, axis=1)
    S_new = states[0] if len(states) == 1 else jnp.concatenate(states, axis=1)
    h_sc[e] = S_new
    ht_ref[0] = S_new


#: tokens of one product-form chunk: the model's ``mamba_chunk_size`` where it
#: divides a chunk slot, else the largest of these that does
SSD_CHUNKS = (256, 128, 64, 32, 16, 8)


def ssd_chunk_scan(dt: jax.Array, x: jax.Array, B: jax.Array, C: jax.Array,
                   a: jax.Array, h0: jax.Array, cont: jax.Array,
                   chunk: int = 256):
    """The Mamba-2 recurrence over ``G`` chunk slots of ``Cs`` packed rows
    each, in the product form (SSD) over chunks of ``Q`` tokens (``chunk``, or
    the largest of :data:`SSD_CHUNKS` under it that divides ``Cs``).

    dt:   [G*Cs, H] float32 (zero on rows that hold no token)
    x:    [G*Cs, E]     a: [H] float32 (negative)
    B, C: [G*Cs, N] (one group), or [G*Cs, Gr, N]: head ``h`` reads group
          ``h // (H / Gr)``
    h0:   [G, N, E] float32, the state a slot starts from
    cont: [G] int32, as :func:`ssm_chunk_scan`

    Returns ``(y [G*Cs, E] float32, hT [G, N, E] float32)``.

    Per chunk, with ``cum_t`` the running sum of ``dt_s a`` inside it (one a
    head): ``y_t = sum_{s<=t} exp(cum_t - cum_s) (C_t . B_s) dt_s x_s +
    exp(cum_t) C_t S`` and ``S' = exp(cum_Q) S + sum_s exp(cum_Q - cum_s) B_s
    (outer) dt_s x_s``: three products a head pair on the MXU in float32
    (``C B^T`` once a chunk and group), the state carried across a slot's
    chunks in on-chip memory. ``exp`` only ever sees differences ``<= 0``."""
    G, N, E = h0.shape
    T, H = dt.shape
    Cs, P = T // G, E // H
    Gr = B.shape[1] if B.ndim == 3 else 1
    Q = next((q for q in SSD_CHUNKS if q <= chunk and Cs % q == 0), 0)
    # a channel block lies inside one group
    Eb = next((eb for eb in (512, 256, 128)
               if E % eb == 0 and (E // Gr) % eb == 0), 0)
    if not (Q and Eb) or LANES % P or N % 8:
        return ssd_chunk_scan_xla(dt, x, B, C, a, h0, cont)
    Hb, nE, nC, bps = Eb // P, E // Eb, T // Q, Cs // Q
    f32 = jnp.float32
    with jax.named_scope("ssd_chunk_scan"):
        dt = dt.astype(f32)
        cum = jnp.cumsum((dt * a.astype(f32)).reshape(nC, Q, nE, Hb), axis=1)
        col = jnp.transpose(cum, (0, 2, 1, 3))                # [nC, nE, Q, Hb]
        row = jnp.transpose(cum, (0, 2, 3, 1))                # [nC, nE, Hb, Q]
        dx = _per_channel(dt, P) * x.astype(f32)
        if Gr == 1:
            Bt = jnp.transpose(B.astype(f32).reshape(nC, Q, N), (0, 2, 1))
        else:       # [nC, Gr, N, Q]
            Bt = jnp.transpose(B.astype(f32).reshape(nC, Q, Gr, N),
                               (0, 2, 3, 1))
        cont = cont.astype(jnp.int32).at[0].set(0)
        slot = lambda tb, e, c: (tb // bps, 0, e)
        heads = lambda tb, e, c: (tb, e, 0, 0)
        if Gr == 1:
            kernel = functools.partial(_ssd_scan_kernel, blocks_per_slot=bps,
                                       P=P)
            b_spec = pl.BlockSpec((1, N, Q), lambda tb, e, c: (tb, 0, 0))
            c_spec = pl.BlockSpec((Q, N), lambda tb, e, c: (tb, 0))
        else:       # channel block e's group of Bt, and of C as [Gr, T, N]
            bpg = E // Gr // Eb
            C = jnp.transpose(C, (1, 0, 2))
            kernel = functools.partial(_ssd_scan_kernel, blocks_per_slot=bps,
                                       P=P, blocks_per_group=bpg)
            b_spec = pl.BlockSpec((1, 1, N, Q),
                                  lambda tb, e, c: (tb, e // bpg, 0, 0))
            c_spec = pl.BlockSpec((1, Q, N),
                                  lambda tb, e, c: (e // bpg, tb, 0))
        call = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(nC, nE),
                in_specs=[pl.BlockSpec((Q, Eb), lambda tb, e, c: (tb, e)),
                          b_spec, c_spec,
                          pl.BlockSpec((1, 1, Q, Hb), heads),
                          pl.BlockSpec((1, 1, Hb, Q), heads),
                          pl.BlockSpec((1, N, Eb), slot)],
                out_specs=[pl.BlockSpec((Q, Eb), lambda tb, e, c: (tb, e)),
                           pl.BlockSpec((1, N, Eb), slot)],
                scratch_shapes=[pltpu.VMEM((nE, N, Eb), f32),
                                pltpu.VMEM((Q, Q), f32)]),
            out_shape=[jax.ShapeDtypeStruct((T, E), f32),
                       jax.ShapeDtypeStruct((G, N, E), f32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=64 << 20),
            interpret=_backend.interpret(),
        )
        y, hT = call(cont, dx, Bt, C.astype(f32), col, row, h0.astype(f32))
    return y, hT


def ssd_chunk_scan_xla(dt, x, B, C, a, h0, cont):
    """:func:`ssd_chunk_scan` in plain XLA, in the RECURRENT form: token by
    token, slot after slot (the Mamba-1 form with ``dt`` and ``a`` repeated
    over each head's channels)."""
    G, N, E = h0.shape
    P = E // dt.shape[1]
    with jax.named_scope("ssd_chunk_scan_xla"):
        A = jnp.broadcast_to(_per_channel(a, P)[None, :], (N, E))
        if B.ndim == 2:
            return ssm_chunk_scan_xla(_per_channel(dt, P), x, B, C, A, h0,
                                      cont)
        # a group's channels are a model of their own under its B and C
        Gr = B.shape[1]
        chan = lambda v: jnp.moveaxis(
            v.reshape(v.shape[:-1] + (Gr, E // Gr)), -2, 0)
        y, hT = jax.vmap(ssm_chunk_scan_xla,
                         in_axes=(0, 0, 1, 1, 0, 0, None))(
            chan(_per_channel(dt, P)), chan(x.astype(jnp.float32)), B, C,
            chan(A), chan(h0), cont)
        return (jnp.moveaxis(y, 0, -2).reshape(-1, E),
                jnp.moveaxis(hT, 0, -2).reshape(h0.shape))
