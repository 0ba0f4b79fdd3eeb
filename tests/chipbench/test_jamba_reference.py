"""The plain Jamba reference (``chipbench/reference/jamba_ref.py``) against
the zoo's dense forward; each of its parts moves the logits when dropped; a
state held in a lower precision moves them; the state-space kernels,
interpreted, against their plain-XLA forms and the reference's
recurrence."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.harness import Registry  # noqa: E402
from chipbench.reference import jamba_ref  # noqa: E402
from deepspeed_tpu.models.jamba import (JambaConfig,  # noqa: E402
                                        JambaForCausalLM)
from deepspeed_tpu.ops.pallas import ssm  # noqa: E402

#: what the engine is held to on the CPU (tests/unit/test_jamba_serving.py)
TOL = 2e-4


@pytest.fixture(scope="module")
def model():
    cfg = JambaConfig.tiny(dtype=jnp.float32)
    net = JambaForCausalLM(cfg)
    params = net.init(jax.random.PRNGKey(3),
                      jnp.zeros((1, 8), jnp.int32))["params"]
    fam = Registry().module("families", "jamba")
    d = {k: getattr(cfg, k) for k in fam.MODEL_KEYS}
    ids = np.random.default_rng(0).integers(0, 256, 48).astype(np.int32)
    weights, hp = fam.reference_weights(params, d), fam.reference_hp(d)
    right = np.asarray(jamba_ref.forward_logits(weights, ids, hp))
    return net, params, weights, hp, ids, right


def rel(got, want):
    return float(np.max(np.abs(np.asarray(got) - want))
                 / np.max(np.abs(want)))


def test_reference_agrees_with_the_zoo(model):
    net, params, _, hp, ids, right = model
    assert hp["kinds"] == ["mamba", "attention", "mamba", "mamba"]
    with jax.default_matmul_precision("highest"):
        zoo = np.asarray(net.apply({"params": params}, ids[None])[0])
    assert rel(zoo, right) < 1e-5


@pytest.mark.parametrize("part", ["conv_history", "inner_norms", "D", "gate"])
def test_reference_changes_when_a_part_is_dropped(model, part):
    """Causality of the convolution (its taps on earlier tokens), the three
    inner norms, the skip term and the gate each move the logits by more than
    twice what the engine is held to."""
    _, _, weights, hp, ids, right = model
    got = jamba_ref.forward_logits(weights, ids, dict(hp, drop=(part,)))
    assert rel(got, right) > 2 * TOL


def test_convolution_is_causal_and_the_recurrence_remembers(model):
    """A later token changes no earlier row; an earlier token changes every
    later row (through the state, far beyond the convolution's four taps)."""
    _, _, weights, hp, ids, right = model
    late = ids.copy()
    late[40] = (late[40] + 1) % 256
    got = np.asarray(jamba_ref.forward_logits(weights, late, hp))
    assert np.array_equal(got[:40], right[:40])
    early = ids.copy()
    early[2] = (early[2] + 1) % 256
    got = np.asarray(jamba_ref.forward_logits(weights, early, hp))
    assert np.array_equal(got[:2], right[:2])
    assert all(rel(got[t], right[t]) > 1e-6 for t in range(2, 48))


def test_a_state_in_a_lower_precision_moves_the_reference(model):
    _, _, weights, hp, ids, right = model
    logits, state = jamba_ref.forward_logits(weights, ids, hp,
                                             with_state=True)
    assert state.shape == (3, 128, 16) and rel(logits, right) == 0
    low, low_state = jamba_ref.forward_logits(
        weights, ids, hp, with_state=True, state_dtype=jnp.bfloat16)
    assert rel(low, right) > 1e-4
    assert 1e-4 < rel(low_state, np.asarray(state)) < 5e-2
    rows = jamba_ref.forward_logits(weights, ids, hp, rows=[5, 47])
    assert rel(rows, right[[5, 47]]) == 0
    # activations in a lower precision: bfloat16 is what the engine keeps,
    # float8 the nearest below it, an order of magnitude further off
    half = rel(jamba_ref.forward_logits(weights, ids, hp,
                                        act_dtype=jnp.bfloat16), right)
    eighth = rel(jamba_ref.forward_logits(weights, ids, hp,
                                          act_dtype=jnp.float8_e4m3fn), right)
    assert 1e-3 < half < 0.1 < eighth


@pytest.mark.parametrize("name", ["dt_proj", "c", "B_C"])
def test_an_input_of_the_recurrence_left_in_float32_moves_the_first_state(
        model, name):
    """``hp["unrounded"]`` (which of the recurrence's inputs a compiler leaves
    in float32 when activations are bfloat16) does nothing at float32 and, at
    bfloat16, moves the first Mamba layer's state by about what bfloat16
    moves anything — what the chip's check compares the engine's with."""
    _, _, weights, hp, ids, right = model
    wide = dict(hp, unrounded=(name,))
    assert rel(jamba_ref.forward_logits(weights, ids, wide), right) == 0
    first = lambda **kw: np.asarray(jamba_ref.forward_logits(
        weights, ids, kw.pop("hp", hp), with_state=True,
        act_dtype=jnp.bfloat16, **kw)[1])[0]
    assert 0 < rel(first(hp=wide), first()) < 2e-2


# --------------------------------------------------------------------------- #
# kernels: interpreted Pallas against the plain-XLA forms and the reference
# --------------------------------------------------------------------------- #

def _inputs(E, G=4, Cs=32, N=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    T = G * Cs
    dt = jax.nn.softplus(jax.random.normal(k[0], (T, E)) - 3.0)
    x = jax.random.normal(k[1], (T, E))
    B, C = jax.random.normal(k[2], (T, N)), jax.random.normal(k[3], (T, N))
    A = -jnp.broadcast_to(jnp.arange(1.0, N + 1)[:, None], (N, E))
    h0 = jax.random.normal(k[4], (G, N, E))
    return dt, x, B, C, A, h0


@pytest.mark.parametrize("E", [256, 5120])
def test_chunk_scan_kernel_against_xla_and_the_reference(E):
    dt, x, B, C, A, h0 = _inputs(E)
    dt = dt.at[100:128].set(0.0)                  # a chunk shorter than its slot
    cont = jnp.asarray([0, 1, 0, 1], jnp.int32)
    y, hT = jax.jit(ssm.ssm_chunk_scan)(dt, x, B, C, A, h0, cont)
    y2, hT2 = jax.jit(ssm.ssm_chunk_scan_xla)(dt, x, B, C, A, h0, cont)
    assert rel(y, np.asarray(y2)) < 1e-5 and rel(hT, np.asarray(hT2)) < 1e-5
    # the reference's own step, token by token, over slots 2 and 3 (one
    # sequence of 64 tokens that starts from h0[2]; rows 100-127 hold none)
    h = np.asarray(h0[2], np.float64)
    for t in range(64, 100):
        d = np.asarray(dt[t], np.float64)
        h = np.exp(d[None] * np.asarray(A)) * h + (d * np.asarray(x[t]))[None] \
            * np.asarray(B[t])[:, None]
        assert rel(y[t], (h * np.asarray(C[t])[:, None]).sum(0)) < 1e-4
    assert rel(hT[3], h) < 1e-5
    assert rel(hT[2], np.asarray(hT[3])) > 1e-3   # slot 3 went on from slot 2


@pytest.mark.parametrize("E", [256, 5120])
def test_decode_step_kernel_updates_its_rows_states_in_place(E):
    dt, x, B, C, A, _ = _inputs(E, G=1, Cs=8)
    pool = jax.random.normal(jax.random.PRNGKey(9), (3, 6, 16, E))
    tails = jax.random.normal(jax.random.PRNGKey(8), (3, 6, 24, E // 8))
    a = jax.random.normal(jax.random.PRNGKey(7), (8, E))
    slots = jnp.asarray([4, 0, 5, 2, 5, 5, 1, 3], jnp.int32)   # 5 = the dump
    y, new, t = (np.asarray(v) for v in jax.jit(ssm.ssm_decode_step)(
        pool, tails, 1, slots, dt, x, B, C, A, a))
    y2, new2, t2 = (np.asarray(v) for v in jax.jit(ssm.ssm_decode_step_xla)(
        pool, tails, 1, slots, dt, x, B, C, A, a))
    pool, tails = np.asarray(pool), np.asarray(tails)
    # a row's tail drops its oldest tap and takes the row's input as newest
    assert np.array_equal(t[1, [0, 1, 2, 3, 4]], t2[1, [0, 1, 2, 3, 4]])
    assert np.array_equal(t[0], tails[0]) and np.array_equal(t[2], tails[2])
    want = np.concatenate([tails[1, 2].reshape(3, E)[1:], np.asarray(a[3:4])])
    assert np.array_equal(t[1, 2].reshape(3, E), want)
    rows = [0, 1, 3, 6, 7]                        # the rows with a slot alone
    assert rel(y[rows], y2[rows]) < 1e-5
    keep = [0, 1, 2, 3, 4]
    assert rel(new[1, keep], new2[1, keep]) < 1e-5
    assert np.array_equal(new[0], pool[0]) and np.array_equal(new[2], pool[2])
    i, s = 3, 2
    h = np.exp(np.asarray(dt[i])[None] * np.asarray(A)) * np.asarray(pool[1, s]) \
        + (np.asarray(dt[i]) * np.asarray(x[i]))[None] * np.asarray(B[i])[:, None]
    assert rel(new[1, s], h) < 1e-5
    assert rel(y[i], (h * np.asarray(C[i])[:, None]).sum(0)) < 1e-4


def test_shapes_the_kernels_refuse_take_the_xla_form():
    dt, x, B, C, A, h0 = _inputs(96, G=2, Cs=4)
    y, hT = ssm.ssm_chunk_scan(dt, x, B, C, A, h0, jnp.zeros((2,), jnp.int32))
    assert y.shape == (8, 96) and hT.shape == (2, 16, 96)
    text = jax.jit(ssm.ssm_chunk_scan).lower(
        dt, x, B, C, A, h0, jnp.zeros((2,), jnp.int32)).as_text()
    assert "pallas" not in text.lower()
