"""The Mamba-2 (SSD) state kernels of ``ops/pallas/ssm.py``, interpreted:
``ssd_chunk_scan`` (the product form over chunks) and ``ssd_decode_step`` (one
token a row, state and tail in the aliased pools) against their plain-XLA
forms and against the token-by-token recurrence of the plain reference
(``chipbench/reference/granite_ref.py::recurrence``)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from deepspeed_tpu.inference.v2.ragged.state_pool import StatePoolConfig  # noqa: E402
from deepspeed_tpu.ops.pallas import ssm  # noqa: E402

H, P, N = 4, 64, 128
E = H * P
F32 = jnp.float32


def draw(seed, T, heads=H, head=P, state=N):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), F32)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1),
                                        (T, heads))), F32)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, (heads,)), F32)
    return dt, f(T, heads * head), f(T, state), f(T, state), a


def recurrent(dt, x, B, C, a, heads=H):
    """The reference's token-by-token recurrence: ``(y [T, E], the last
    state as the pool lays it out [N, E])``."""
    from chipbench.reference import granite_ref
    T, width = x.shape
    y, S = granite_ref.recurrence(dt, x.reshape(T, heads, -1), B, C, a)
    return y.reshape(T, width), S.reshape(width, -1).T


def close(got, want, tol=2e-5):
    got, want = np.asarray(got), np.asarray(want)
    return np.max(np.abs(got - want)) <= tol * max(1.0, np.max(np.abs(want)))


@pytest.mark.parametrize("slot, chunk", [(64, 256), (64, 32), (48, 32),
                                         (24, 256), (8, 8)])
def test_chunk_scan_is_the_recurrence(slot, chunk):
    """One slot from a zero state: the product form over chunks of ``chunk``
    (or the divisor of the slot under it: 48 rows go as chunks of 16, 24 as
    chunks of 8) equals the recurrent form, outputs and last state."""
    dt, x, B, C, a = draw(slot, slot)
    h0 = jnp.zeros((1, N, E), F32)
    y, hT = ssm.ssd_chunk_scan(dt, x, B, C, a, h0, jnp.zeros((1,), jnp.int32),
                               chunk=chunk)
    want_y, want_h = recurrent(dt, x, B, C, a)
    assert close(y, want_y) and close(hT[0], want_h)


@pytest.mark.parametrize("form", ["kernel", "xla"])
def test_a_prompt_split_over_chunk_slots_continues_its_state(form):
    """Three slots of 32: the second continues the first (``cont``), the
    third starts from its own ``h0``; 70 tokens of one sequence over slots
    one and two equal the recurrence over them in one go."""
    scan = ssm.ssd_chunk_scan if form == "kernel" else ssm.ssd_chunk_scan_xla
    dt, x, B, C, a = draw(1, 96)
    rng = np.random.default_rng(2)
    h0 = jnp.asarray(rng.standard_normal((3, N, E)), F32).at[0].set(0.0)
    cont = jnp.asarray([0, 1, 0], jnp.int32)
    y, hT = scan(dt, x, B, C, a, h0, cont)
    want_y, want_h = recurrent(dt[:64], x[:64], B[:64], C[:64], a)
    assert close(y[:64], want_y) and close(hT[1], want_h)
    # the third slot against the XLA form from its own start
    y3, h3 = ssm.ssd_chunk_scan_xla(dt[64:], x[64:], B[64:], C[64:], a,
                                    h0[2:], jnp.zeros((1,), jnp.int32))
    assert close(y[64:], y3) and close(hT[2], h3[0])


def test_rows_with_dt_zero_leave_the_state_alone():
    """A chunk shorter than its slot: rows past its token count have
    ``dt = 0`` and the state after the slot is the state after the chunk."""
    dt, x, B, C, a = draw(3, 64)
    live = 37
    masked = dt.at[live:].set(0.0)
    h0 = jnp.zeros((1, N, E), F32)
    y, hT = ssm.ssd_chunk_scan(masked, x, B, C, a, h0,
                               jnp.zeros((1,), jnp.int32))
    want_y, want_h = recurrent(dt[:live], x[:live], B[:live], C[:live], a)
    assert close(y[:live], want_y) and close(hT[0], want_h)
    assert np.isfinite(np.asarray(y)).all()


@pytest.mark.parametrize("head", [32, 128])
def test_chunk_scan_at_other_head_sizes(head):
    """Four heads of 32 a lane tile, or one of 128."""
    heads = 256 // head
    dt, x, B, C, a = draw(4, 32, heads=heads, head=head)
    h0 = jnp.zeros((1, N, 256), F32)
    y, hT = ssm.ssd_chunk_scan(dt, x, B, C, a, h0, jnp.zeros((1,), jnp.int32))
    want_y, want_h = recurrent(dt, x, B, C, a, heads=heads)
    assert close(y, want_y) and close(hT[0], want_h)


def test_chunk_scan_falls_back_where_the_kernel_refuses():
    """A slot of 12 rows (no multiple of 8): the XLA form, same numbers."""
    dt, x, B, C, a = draw(5, 12)
    h0 = jnp.zeros((1, N, E), F32)
    y, hT = ssm.ssd_chunk_scan(dt, x, B, C, a, h0, jnp.zeros((1,), jnp.int32))
    want_y, want_h = recurrent(dt, x, B, C, a)
    assert close(y, want_y) and close(hT[0], want_h)


def pools(seed, layers=2, slots=5, d_conv=4):
    cfg = StatePoolConfig(num_layers=layers, num_slots=slots - 1, d_inner=E,
                          d_state=N, d_conv=d_conv, conv_dim=E + 2 * N)
    rng = np.random.default_rng(seed)
    shapes = jax.eval_shape(cfg.zeros)
    return cfg, tuple(jnp.asarray(rng.standard_normal(s.shape), F32)
                      for s in shapes)


@pytest.mark.parametrize("rows", [[2, 0, 3], [1]])
def test_decode_step_is_its_xla_form_and_the_recurrence(rows):
    cfg, (pool, tails) = pools(6)
    S = len(rows)
    dt, x, B, C, a = draw(7, S)
    new = jnp.asarray(np.random.default_rng(8).standard_normal(
        (S, cfg.conv_dim)), F32)
    slots = jnp.asarray(rows, jnp.int32)
    got = ssm.ssd_decode_step(pool, tails, 1, slots, dt, x, B, C, a, new)
    want = ssm.ssd_decode_step_xla(pool, tails, 1, slots, dt, x, B, C, a, new)
    assert all(close(g, w) for g, w in zip(got, want))
    y, pool2, tails2 = got
    for i, s in enumerate(rows):
        # one step of the recurrence from the slot's state
        old = np.asarray(pool[1, s])                       # [N, E]
        decay = np.repeat(np.exp(np.asarray(dt[i] * a)), P)
        dx = np.repeat(np.asarray(dt[i]), P) * np.asarray(x[i])
        want_h = decay * old + np.asarray(B[i])[:, None] * dx
        assert close(pool2[1, s], want_h)
        assert close(y[i], np.asarray(C[i]) @ want_h)
        # the tail dropped its oldest tap and took the new input, padded
        assert close(tails2[1, s, :16], tails[1, s, 8:])
        flat = np.asarray(tails2[1, s, 16:]).reshape(-1)
        assert close(flat[:cfg.conv_dim], new[i])
        assert not flat[cfg.conv_dim:].any()
    untouched = [s for s in range(5) if s not in rows]
    assert close(pool2[1, untouched], pool[1, untouched], 0)
    assert close(pool2[0], pool[0], 0) and close(tails2[0], tails[0], 0)


def test_decode_rows_that_share_the_dump_slot_leave_one_state_there():
    cfg, (pool, tails) = pools(9)
    dt, x, B, C, a = draw(10, 3)
    new = jnp.zeros((3, cfg.conv_dim), F32)
    slots = jnp.asarray([4, 1, 4], jnp.int32)
    y, pool2, _ = ssm.ssd_decode_step(pool, tails, 0, slots, dt, x, B, C, a,
                                      new)
    alone = ssm.ssd_decode_step_xla(pool, tails, 0, slots[1:2], dt[1:2],
                                    x[1:2], B[1:2], C[1:2], a, new[1:2])
    assert close(y[1], alone[0][0]) and close(pool2[0, 1], alone[1][0, 1])
    assert np.isfinite(np.asarray(pool2)).all()


def test_pool_bytes_a_sequence_a_layer():
    """Granite's widths: 128 x 64 x 128 float32 of state and three taps over
    the 8,448 convolved channels padded to 9,216."""
    cfg = StatePoolConfig(num_layers=9, num_slots=72, d_inner=8192,
                          d_state=128, d_conv=4, conv_dim=8448)
    assert cfg.conv_width == 9216
    assert cfg.bytes_per_slot() == 9 * (128 * 64 * 128 * 4 + 3 * 9216 * 4)
    ssm_shape, conv_shape = jax.eval_shape(cfg.zeros)
    assert ssm_shape.shape == (9, 73, 128, 8192)
    assert conv_shape.shape == (9, 73, 24, 1152)
    assert cfg.total_bytes() == 73 * cfg.bytes_per_slot()
    # one slot of one layer is whole 8 x 128 tiles of 32 bits
    assert (128 * 8192) % 1024 == 0 and (24 * 1152) % 1024 == 0


def test_mamba1_pool_shapes_are_what_they_were():
    cfg = StatePoolConfig(num_layers=26, num_slots=160, d_inner=5120,
                          d_state=16, d_conv=4)
    assert cfg.conv_width == 5120
    assert cfg.bytes_per_slot() == 26 * 4 * 5120 * (16 + 3)
    ssm_shape, conv_shape = jax.eval_shape(cfg.zeros)
    assert ssm_shape.shape == (26, 161, 16, 5120)
    assert conv_shape.shape == (26, 161, 24, 640)


# --------------------------------------------------------------------------- #
# more than one group of B and C (nemotron_h: 8; head h reads group h // (H/G))
# --------------------------------------------------------------------------- #

def draw_groups(seed, T, groups, heads=8):
    """``heads`` heads of 64 in ``groups`` groups: ``B``, ``C`` ``[T, G, N]``
    (E 512: with 2 groups a channel block of 256 lies in one group, with 8
    a group is 64 channels and the kernels hand over to their XLA forms)."""
    rng = np.random.default_rng(seed)
    dt, x, _, _, a = draw(seed, T, heads=heads)
    f = lambda: jnp.asarray(rng.standard_normal((T, groups, N)), F32)
    return dt, x, f(), f(), a


def per_token_loop(dt, x, B, C, a, h0=None):
    """The recurrence written out in numpy, a token and a head at a time:
    ``(y [T, E], the last state [N, E])``."""
    dt, x, B, C, a = (np.asarray(v, np.float64) for v in (dt, x, B, C, a))
    T, heads = dt.shape
    G = B.shape[1]
    S = np.zeros((heads, P, N)) if h0 is None else np.asarray(
        h0, np.float64).T.reshape(heads, P, N)
    ys = np.zeros((T, heads, P))
    for t in range(T):
        for h in range(heads):
            g = h // (heads // G)
            S[h] = np.exp(dt[t, h] * a[h]) * S[h] + dt[t, h] * np.outer(
                x[t, h * P:(h + 1) * P], B[t, g])
            ys[t, h] = S[h] @ C[t, g]
    return ys.reshape(T, -1), S.reshape(heads * P, N).T


@pytest.mark.parametrize("form", ["kernel", "xla"])
@pytest.mark.parametrize("groups", [2, 8])
def test_grouped_chunk_scan_is_the_per_token_loop(groups, form):
    """Two slots of 32, the second continuing the first: the product form
    with a ``C B^T`` a group (2 groups: the kernel; 8 groups of 64 channels:
    what it hands to the XLA form) and the XLA form itself against the loop
    over tokens and heads, outputs and last state; and a head that read
    another group's B and C would not pass."""
    scan = ssm.ssd_chunk_scan if form == "kernel" else ssm.ssd_chunk_scan_xla
    dt, x, B, C, a = draw_groups(11 + groups, 64, groups)
    width = x.shape[1]
    h0 = jnp.zeros((2, N, width), F32)
    y, hT = scan(dt, x, B, C, a, h0, jnp.asarray([0, 1], jnp.int32))
    want_y, want_h = per_token_loop(dt, x, B, C, a)
    assert close(y, want_y) and close(hT[1], want_h)
    wrong_y, _ = per_token_loop(dt, x, B[:, ::-1], C[:, ::-1], a)
    assert not close(y, wrong_y, 1e-2)


def test_grouped_chunk_scan_kernel_is_its_xla_form_from_a_state():
    """16 heads in 2 groups (E 1,024: two channel blocks of 512, one a
    group), a slot that starts from its own ``h0``."""
    dt, x, B, C, a = draw_groups(21, 32, 2, heads=16)
    h0 = jnp.asarray(np.random.default_rng(22).standard_normal(
        (1, N, 1024)), F32)
    cont = jnp.zeros((1,), jnp.int32)
    got = ssm.ssd_chunk_scan(dt, x, B, C, a, h0, cont)
    want = ssm.ssd_chunk_scan_xla(dt, x, B, C, a, h0, cont)
    assert all(close(g, w) for g, w in zip(got, want))
    loop_y, loop_h = per_token_loop(dt, x, B, C, a, h0[0])
    assert close(got[0], loop_y) and close(got[1][0], loop_h)


@pytest.mark.parametrize("groups, heads", [(2, 8), (8, 16), (2, 64)])
def test_grouped_decode_step_is_its_xla_form_and_the_loop(groups, heads):
    """The one-token kernel with the groups of a channel block side by side
    (8 heads in 2 groups: one block of 512 holds both; 16 heads in 8: groups
    of 128 channels, eight a block) or a group over several blocks (64 heads
    in 2 groups: E 4,096 in blocks of 2,048), against its XLA form and one
    step of the loop from the slot's state."""
    width = heads * P
    cfg = StatePoolConfig(num_layers=2, num_slots=3, d_inner=width, d_state=N,
                          d_conv=4, conv_dim=width + 2 * groups * N)
    rng = np.random.default_rng(30 + groups)
    pool, tails = (jnp.asarray(rng.standard_normal(s.shape), F32)
                   for s in jax.eval_shape(cfg.zeros))
    rows = [2, 0, 3]
    dt, x, B, C, a = draw_groups(31, 3, groups, heads=heads)
    new = jnp.asarray(rng.standard_normal((3, cfg.conv_dim)), F32)
    slots = jnp.asarray(rows, jnp.int32)
    got = ssm.ssd_decode_step(pool, tails, 1, slots, dt, x, B, C, a, new)
    want = ssm.ssd_decode_step_xla(pool, tails, 1, slots, dt, x, B, C, a, new)
    assert all(close(g, w) for g, w in zip(got, want))
    y, pool2, tails2 = got
    for i, s in enumerate(rows):
        loop_y, loop_h = per_token_loop(dt[i:i + 1], x[i:i + 1], B[i:i + 1],
                                        C[i:i + 1], a, pool[1, s])
        assert close(pool2[1, s], loop_h) and close(y[i], loop_y[0])
        flat = np.asarray(tails2[1, s, 16:]).reshape(-1)
        assert close(flat[:cfg.conv_dim], new[i])
    assert close(pool2[0], pool[0], 0) and close(pool2[1, 1], pool[1, 1], 0)


def test_one_group_given_with_a_group_axis_is_the_ungrouped_call():
    """``[T, 1, N]`` and ``[T, N]`` are the same recurrence."""
    dt, x, B, C, a = draw(40, 32)
    h0 = jnp.zeros((1, N, E), F32)
    cont = jnp.zeros((1,), jnp.int32)
    flat = ssm.ssd_chunk_scan_xla(dt, x, B, C, a, h0, cont)
    grouped = ssm.ssd_chunk_scan_xla(dt, x, B[:, None], C[:, None], a, h0,
                                     cont)
    assert all(close(g, w) for g, w in zip(grouped, flat))


def test_nemotron_pool_bytes_a_sequence_a_layer():
    """Nemotron 3 Nano's widths: 64 x 64 x 128 float32 of state (2 MiB) and
    three taps over the 6,144 convolved channels (x and 8 groups of B and
    C), whole tiles as they are."""
    cfg = StatePoolConfig(num_layers=7, num_slots=144, d_inner=4096,
                          d_state=128, d_conv=4, conv_dim=4096 + 2 * 8 * 128)
    assert cfg.conv_width == 6144
    assert cfg.bytes_per_slot() == 7 * (2 * 2**20 + 72 * 2**10) == 15196160
    ssm_shape, conv_shape = jax.eval_shape(cfg.zeros)
    assert ssm_shape.shape == (7, 145, 128, 4096)
    assert conv_shape.shape == (7, 145, 24, 768)
