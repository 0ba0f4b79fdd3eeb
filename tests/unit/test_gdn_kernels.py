"""The gated delta-rule kernels (``ops/pallas/gdn.py``) against the
sequential recurrence written out in numpy: the chunked scan (the Pallas
kernel through the interpreter, and its XLA twin) over ragged lengths,
continued across calls, at the edges of ``beta`` and of the decay; the
one-token step against one step of it, the dump slot untouched."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import gdn

N, P = 128, 128


def recurrence(q, k, v, g, beta, S0):
    """Token by token in float64: q, k [T, Hk, N], v [T, Hv, P], g, beta
    [T, Hv], S0 [N, Hv * P] -> (o [T, Hv * P], S [N, Hv * P])."""
    T, Hv = g.shape
    rep = Hv // q.shape[1]
    S = np.asarray(S0, np.float64).reshape(N, Hv, P).copy()
    out = np.zeros((T, Hv, P))
    for t in range(T):
        for h in range(Hv):
            kt, qt = k[t, h // rep].astype(np.float64), q[t, h // rep]
            Sd = np.exp(g[t, h]) * S[:, h]
            S[:, h] = Sd + beta[t, h] * np.outer(kt, v[t, h] - Sd.T @ kt)
            out[t, h] = S[:, h].T @ qt
    return out.reshape(T, Hv * P), S.reshape(N, Hv * P)


def draw(rng, T, Hk, Hv, beta=None, decay=None):
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.standard_normal((T, Hk, N))) * N ** -0.5
    k = unit(rng.standard_normal((T, Hk, N)))
    v = rng.standard_normal((T, Hv, P))
    b = rng.uniform(0.05, 0.95, (T, Hv)) if beta is None \
        else np.full((T, Hv), beta)
    g = np.log(rng.uniform(0.5, 1.0, (T, Hv))) if decay is None \
        else np.full((T, Hv), np.log(decay))
    return tuple(x.astype(np.float32) for x in (q, k, v, g, b))


def flat(x):
    return jnp.asarray(x.reshape(x.shape[0], -1))


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / (np.mean(b ** 2) + 1e-30)))


SCANS = {"pallas": gdn.gdn_chunk_scan, "xla": gdn.gdn_chunk_scan_xla}


@pytest.mark.parametrize("which", list(SCANS))
@pytest.mark.parametrize("edge", [
    {}, {"beta": 1.0}, {"beta": 1e-4}, {"decay": 1.0}, {"decay": 1e-6},
    {"beta": 1.0, "decay": 1.0}])
def test_chunk_scan_is_the_recurrence(which, edge):
    """Three slots of 128 rows, the second continuing the first, the third a
    sequence of its own from a state handed in, ragged lengths (the last slot
    holds 70 tokens: its other rows carry g = 0, beta = 0)."""
    rng = np.random.default_rng(3)
    Hk, Hv, Cs = 1, 2, 128
    a = draw(rng, 2 * Cs, Hk, Hv, **edge)
    b = draw(rng, 70, Hk, Hv, **edge)
    S0 = rng.standard_normal((N, Hv * P)).astype(np.float32)
    want_a, Sa = recurrence(*a, np.zeros((N, Hv * P)))
    want_b, Sb = recurrence(*b, S0)
    pad = lambda x: np.concatenate(
        [x, np.zeros((Cs - 70,) + x.shape[1:], np.float32)])
    q, k, v, g, beta = (np.concatenate([x, pad(y)]) for x, y in zip(a, b))
    h0 = jnp.stack([jnp.zeros((N, Hv * P)), jnp.ones((N, Hv * P)),
                    jnp.asarray(S0)])
    y, hT = SCANS[which](flat(q), flat(k), flat(v), jnp.asarray(g),
                         jnp.asarray(beta), h0, jnp.asarray([0, 1, 0]))
    assert rel(y[:2 * Cs], want_a) < 2e-5
    assert rel(y[2 * Cs:2 * Cs + 70], want_b) < 2e-5
    assert rel(hT[1], Sa) < 2e-5 and rel(hT[2], Sb) < 2e-5


@pytest.mark.parametrize("chunk", [64, 128, 16])
def test_any_chunk_is_the_same_recurrence(chunk):
    """Two key heads serving four value heads, the chunk varied: the chunk
    is no part of the mathematics."""
    rng = np.random.default_rng(chunk)
    Hk, Hv, Cs = 2, 4, 128
    q, k, v, g, beta = draw(rng, Cs, Hk, Hv)
    want, S = recurrence(q, k, v, g, beta, np.zeros((N, Hv * P)))
    y, hT = gdn.gdn_chunk_scan(flat(q), flat(k), flat(v), jnp.asarray(g),
                               jnp.asarray(beta),
                               jnp.zeros((1, N, Hv * P)), jnp.zeros((1,), int),
                               chunk=chunk)
    assert rel(y, want) < 2e-5 and rel(hT[0], S) < 2e-5


def test_a_sequence_resumes_across_calls():
    """A call's last state handed to the next call as ``h0`` gives what one
    call over all the tokens gives."""
    rng = np.random.default_rng(11)
    Hk, Hv, Cs = 1, 2, 64
    q, k, v, g, beta = draw(rng, 2 * Cs, Hk, Hv)
    want, S = recurrence(q, k, v, g, beta, np.zeros((N, Hv * P)))
    h = jnp.zeros((1, N, Hv * P))
    ys = []
    for i in range(2):
        part = slice(i * Cs, (i + 1) * Cs)
        y, h = gdn.gdn_chunk_scan(flat(q[part]), flat(k[part]), flat(v[part]),
                                  jnp.asarray(g[part]), jnp.asarray(beta[part]),
                                  h, jnp.zeros((1,), int))
        ys.append(y)
    assert rel(jnp.concatenate(ys), want) < 2e-5 and rel(h[0], S) < 2e-5


def test_repeated_keys_do_not_cancel():
    """The same key written 128 times with beta 1 and no decay: the powers of
    ``A`` a Neumann series would sum reach 1e37, block substitution stays at
    rounding."""
    rng = np.random.default_rng(5)
    q, k, v, g, beta = draw(rng, 128, 1, 2, beta=1.0, decay=1.0)
    k[:] = k[0]
    want, S = recurrence(q, k, v, g, beta, np.zeros((N, 2 * P)))
    y, hT = gdn.gdn_chunk_scan(flat(q), flat(k), flat(v), jnp.asarray(g),
                               jnp.asarray(beta), jnp.zeros((1, N, 2 * P)),
                               jnp.zeros((1,), int))
    assert rel(y, want) < 1e-4 and rel(hT[0], S) < 1e-4


def test_bfloat16_activations_take_the_one_pass_products():
    """q, k and v as the engine hands them (bfloat16 values): the scan still
    follows the recurrence on those values."""
    rng = np.random.default_rng(9)
    q, k, v, g, beta = draw(rng, 64, 1, 2)
    bf = lambda x: jnp.asarray(x.reshape(x.shape[0], -1), jnp.bfloat16)
    back = lambda x, like: np.asarray(x.astype(jnp.float32)).reshape(
        like.shape)
    want, S = recurrence(back(bf(q), q), back(bf(k), k), back(bf(v), v), g,
                         beta, np.zeros((N, 2 * P)))
    y, hT = gdn.gdn_chunk_scan(bf(q), bf(k), bf(v), jnp.asarray(g),
                               jnp.asarray(beta), jnp.zeros((1, N, 2 * P)),
                               jnp.zeros((1,), int))
    assert rel(y, want) < 2e-5 and rel(hT[0], S) < 2e-5


STEPS = {"pallas": gdn.gdn_decode_step, "xla": gdn.gdn_decode_step_xla}


@pytest.mark.parametrize("which", list(STEPS))
def test_decode_step_is_one_step_of_it(which):
    """Three rows in slots 4, 0 and 2 of layer 1 of a pool of two layers and
    five slots + the dump slot: their states and tails move, nothing else
    does; the tail drops its oldest tap and takes the new input."""
    rng = np.random.default_rng(2)
    Hk, Hv, Lm, NS, K = 2, 4, 2, 6, 4
    W = 2 * Hk * N + Hv * P
    pool = rng.standard_normal((Lm, NS, N, Hv * P)).astype(np.float32)
    tails = rng.standard_normal((Lm, NS, (K - 1) * 8, W // 8)).astype(
        np.float32)
    slots = np.asarray([4, 0, 2], np.int32)
    q, k, v, g, beta = draw(rng, 3, Hk, Hv)
    new = rng.standard_normal((3, W)).astype(np.float32)
    y, pool2, tails2 = jax.jit(STEPS[which])(
        jnp.asarray(pool), jnp.asarray(tails), jnp.int32(1),
        jnp.asarray(slots), jnp.asarray(g), jnp.asarray(beta), flat(q),
        flat(k), flat(v), jnp.asarray(new))
    pool2, tails2 = np.asarray(pool2), np.asarray(tails2)
    for i, slot in enumerate(slots):
        want, S = recurrence(q[i:i + 1], k[i:i + 1], v[i:i + 1], g[i:i + 1],
                             beta[i:i + 1], pool[1, slot])
        assert rel(y[i], want[0]) < 1e-5 and rel(pool2[1, slot], S) < 1e-5
        assert (tails2[1, slot, :16] == tails[1, slot, 8:]).all()
        assert (tails2[1, slot, 16:].reshape(-1) == new[i]).all()
    untouched = [(0, s) for s in range(NS)] + [(1, 1), (1, 3), (1, 5)]
    for l, s in untouched:
        assert (pool2[l, s] == pool[l, s]).all()
        assert (tails2[l, s] == tails[l, s]).all()


def test_shapes_the_kernels_refuse_take_the_twins():
    """Value heads of 64 (half a lane tile): both entry points fall back to
    plain XLA and still give the recurrence."""
    rng = np.random.default_rng(4)
    Hv, Pv = 2, 64
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.standard_normal((16, 1, N))).astype(np.float32)
    k = unit(rng.standard_normal((16, 1, N))).astype(np.float32)
    v = rng.standard_normal((16, Hv * Pv)).astype(np.float32)
    g = np.log(rng.uniform(0.5, 1, (16, Hv))).astype(np.float32)
    beta = rng.uniform(0, 1, (16, Hv)).astype(np.float32)
    y, hT = gdn.gdn_chunk_scan(flat(q), flat(k), jnp.asarray(v),
                               jnp.asarray(g), jnp.asarray(beta),
                               jnp.zeros((1, N, Hv * Pv)),
                               jnp.zeros((1,), int))
    S = np.zeros((N, Hv, Pv))
    for t in range(16):
        for h in range(Hv):
            Sd = np.exp(g[t, h]) * S[:, h]
            S[:, h] = Sd + beta[t, h] * np.outer(
                k[t, 0], v[t].reshape(Hv, Pv)[h] - Sd.T @ k[t, 0])
    assert rel(hT[0], S.reshape(N, Hv * Pv)) < 1e-5
