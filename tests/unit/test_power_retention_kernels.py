"""The power-retention kernels (``ops/pallas/power_retention.py``) against
the layer's two forms written out in numpy (float64): the attention form (all
pairs, scores squared and decayed, one division) and the state form token by
token. The expansion's identity; the chunked scan (the Pallas kernel through
the interpreter at three chunk lengths, and its XLA twin) over ragged lengths,
a slot that continues the one before it and one that starts from a state
handed in; the one-token step in a pool, the dump slot and the other slots
untouched; bfloat16 rows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import power_retention as pr

EPS = 1e-6


def attention_form(q, k, v, lg):
    """q [T, Hk, G, d], k, v [T, Hk, d], lg [T, Hk] -> y [T, Hk, G, d]: every
    pair ``s <= t``, from an empty state."""
    q, k, v, lg = (np.asarray(x, np.float64) for x in (q, k, v, lg))
    T, d = k.shape[0], k.shape[-1]
    c = np.cumsum(lg, axis=0)                                   # [T, Hk]
    w = np.einsum("thgd,shd->hgts", q, k) ** 2 / d
    w = w * np.exp(np.clip(c.T[:, None, :, None] - c.T[:, None, None, :],
                           None, 0.0))
    w = np.where(np.tril(np.ones((T, T), bool)), w, 0.0)
    return np.einsum("hgts,shd->thgd", w, v) \
        / (w.sum(-1).transpose(2, 0, 1)[..., None] + EPS)


def state_form(q, k, v, lg, h0, Hk, d):
    """The recurrence from the state ``h0`` ``[N, D]`` (the pool's layout):
    (y [T, Hk, G, d], the state after the last token [N, D])."""
    i, j, m = pr.expansion(d)
    q, k, v, lg = (np.asarray(x, np.float64) for x in (q, k, v, lg))
    h = np.asarray(h0, np.float64).copy()
    S = h[:Hk * d].reshape(Hk, d, -1)
    z = h[Hk * d:Hk * d + Hk]
    ys = []
    for t in range(k.shape[0]):
        pk = k[t][:, i] * k[t][:, j] * m                        # [Hk, D]
        g = np.exp(lg[t])
        S = g[:, None, None] * S + v[t][:, :, None] * pk[:, None, :]
        z = g[:, None] * z + pk
        pq = q[t][..., i] * q[t][..., j] * (m > 0)              # [Hk, G, D]
        ys.append(np.einsum("hcD,hgD->hgc", S, pq)
                  / (np.einsum("hD,hgD->hg", z, pq)[..., None] + d * EPS))
    h[:Hk * d] = S.reshape(Hk * d, -1)
    h[Hk * d:Hk * d + Hk] = z
    return np.stack(ys), h


def draw(rng, T, Hk, G, d, dtype=np.float32):
    """Rows as the layer hands them: q and k of RMS 1 a head, gates slow in
    one head and fast in the next."""
    unit = lambda x: x / np.sqrt((x * x).mean(-1, keepdims=True))
    q = unit(rng.standard_normal((T, Hk, G, d)))
    k = unit(rng.standard_normal((T, Hk, d)))
    v = rng.standard_normal((T, Hk, d))
    g = np.where(np.arange(Hk) % 2 == 0, rng.uniform(0.98, 0.9999, (T, Hk)),
                 rng.uniform(0.5, 0.9, (T, Hk)))
    as_rows = lambda x: np.asarray(jnp.asarray(x, dtype).astype(jnp.float32))
    return as_rows(q), as_rows(k), as_rows(v), np.log(g).astype(np.float32)


def flat(x, dtype=jnp.float32):
    return jnp.asarray(x.reshape(x.shape[0], -1), dtype)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / (np.mean(b ** 2) + 1e-30)))


@pytest.mark.parametrize("d", [8, 16, 128])
def test_the_expansion_is_the_square_of_the_dot_product(d):
    """``pq(a) . pk(b) = (a . b)^2`` on ``d (d + 1) / 2`` pairs, each pair of
    the upper triangle held once; a key's entries of bfloat16 values are
    exact float32."""
    rng = np.random.default_rng(d)
    a = jnp.asarray(rng.standard_normal((5, d)), jnp.bfloat16)
    b = jnp.asarray(rng.standard_normal((5, d)), jnp.bfloat16)
    i, j, m = pr.expansion(d)
    assert len(i) == pr.state_cols(d) == d * (d // 2 + 1)
    pairs = {(min(x, y), max(x, y)) for x, y, w in zip(i, j, m) if w > 0}
    assert len(pairs) == int((m > 0).sum()) == d * (d + 1) // 2
    got = (np.asarray(pr.expand(a, False), np.float64)
           * np.asarray(pr.expand(b, True), np.float64)).sum(-1)
    a64, b64 = np.asarray(a, np.float64), np.asarray(b, np.float64)
    np.testing.assert_allclose(got, (a64 * b64).sum(-1) ** 2, rtol=1e-9)
    exact = (b64[:, i] * b64[:, j] * m).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(pr.expand(b, True)), exact)


def test_state_form_is_the_attention_form():
    """The two forms of the module's docstring are one function (numpy,
    float64): what every other test here leans on."""
    rng = np.random.default_rng(0)
    Hk, G, d, T = 2, 2, 16, 40
    q, k, v, lg = draw(rng, T, Hk, G, d)
    N, D = pr.state_rows(Hk, d), pr.state_cols(d)
    y, _ = state_form(q, k, v, lg, np.zeros((N, D)), Hk, d)
    np.testing.assert_allclose(y, attention_form(q, k, v, lg), rtol=1e-9,
                               atol=1e-12)


SCANS = [("xla", 0, 16), ("pallas", 8, 16), ("pallas", 16, 16),
         ("pallas", 32, 16), ("pallas", 32, 128)]


@pytest.mark.parametrize("which,chunk,d", SCANS)
def test_chunk_scan_is_both_forms(which, chunk, d):
    """Three slots of 32 rows: the second continues the first (64 tokens
    from an empty state: the attention form), the third is a sequence of its
    own from a state handed in, 20 tokens long (its other rows carry k = 0
    and log g = 0): the state form. Chunks of 8, 16 and 32."""
    rng = np.random.default_rng(3)
    Hk, G, Cs = 2, 2, 32
    if d == 128:
        Hk = 1
    N, D = pr.state_rows(Hk, d), pr.state_cols(d)
    a = draw(rng, 2 * Cs, Hk, G, d)
    b = draw(rng, 20, Hk, G, d)
    # a state some earlier tokens left: entries >= 0 in z, as sums of squares
    prior = draw(rng, 9, Hk, G, d)
    _, S0 = state_form(*prior, np.zeros((N, D)), Hk, d)
    want_a = attention_form(*a)
    _, Sa = state_form(*a, np.zeros((N, D)), Hk, d)
    want_b, Sb = state_form(*b, S0, Hk, d)
    pad = lambda x: np.concatenate(
        [x, np.zeros((Cs - 20,) + x.shape[1:], np.float32)])
    q, k, v, lg = (np.concatenate([x, pad(y)]) for x, y in zip(a, b))
    h0 = jnp.stack([jnp.zeros((N, D)), jnp.ones((N, D)),
                    jnp.asarray(S0, jnp.float32)])
    cont = jnp.asarray([0, 1, 0], jnp.int32)
    args = (flat(q), flat(k), flat(v), jnp.asarray(lg), h0, cont)
    if which == "xla":
        y, hT = pr.pr_chunk_scan_xla(*args, eps=EPS)
    else:
        y, hT = pr.pr_chunk_scan(*args, chunk=chunk, eps=EPS)
    y = np.asarray(y).reshape(3 * Cs, Hk, G, d)
    assert rel(y[:2 * Cs], want_a) < 2e-5
    assert rel(y[2 * Cs:2 * Cs + 20], want_b) < 2e-5
    assert rel(hT[1], Sa) < 2e-6
    assert rel(hT[2], Sb) < 2e-6
    assert np.isfinite(np.asarray(y)).all()


@pytest.mark.parametrize("which", ["pallas", "xla"])
def test_chunk_scan_of_bfloat16_rows(which):
    """bfloat16 q, k and v (what the layer hands the kernel): their products
    are exact, so the result is the float64 forms' of those very values."""
    rng = np.random.default_rng(5)
    Hk, G, d, Cs = 2, 2, 16, 32
    N, D = pr.state_rows(Hk, d), pr.state_cols(d)
    q, k, v, lg = draw(rng, Cs, Hk, G, d, jnp.bfloat16)
    want = attention_form(q, k, v, lg)
    _, S = state_form(q, k, v, lg, np.zeros((N, D)), Hk, d)
    bf = jnp.bfloat16
    args = (flat(q, bf), flat(k, bf), flat(v, bf), jnp.asarray(lg),
            jnp.zeros((1, N, D)), jnp.zeros((1,), jnp.int32))
    y, hT = (pr.pr_chunk_scan if which == "pallas"
             else pr.pr_chunk_scan_xla)(*args, eps=EPS)
    assert rel(np.asarray(y).reshape(Cs, Hk, G, d), want) < 2e-5
    assert rel(hT[0], S) < 2e-6


@pytest.mark.parametrize("which,d,Hk", [("pallas", 16, 2), ("pallas", 128, 1),
                                        ("xla", 16, 2)])
def test_decode_step_is_one_token_of_the_recurrence(which, d, Hk):
    """Three rows in a pool of two layers and five slots; the second layer's
    slots 3, 0 and 1 take a token, every other slot of both layers stays as
    it was."""
    rng = np.random.default_rng(7)
    G, Lm, NS = 2, 2, 5
    N, D = pr.state_rows(Hk, d), pr.state_cols(d)
    prior = draw(rng, 6, Hk, G, d)
    pool = np.zeros((Lm, NS, N, D), np.float32)
    for n in range(NS):
        _, pool[1, n] = state_form(*draw(rng, 6, Hk, G, d), np.zeros((N, D)),
                                   Hk, d)
        pool[0, n] = pool[1, n] * 0.5
    del prior
    slots = np.asarray([3, 0, 1], np.int32)
    q, k, v, lg = draw(rng, 3, Hk, G, d)
    step = pr.pr_decode_step if which == "pallas" else pr.pr_decode_step_xla
    y, new = step(jnp.asarray(pool), jnp.int32(1), jnp.asarray(slots),
                  jnp.asarray(lg), flat(q), flat(k), flat(v), eps=EPS)
    new = np.asarray(new)
    for r, n in enumerate(slots):
        want, S = state_form(q[r:r + 1], k[r:r + 1], v[r:r + 1], lg[r:r + 1],
                             pool[1, n], Hk, d)
        assert rel(np.asarray(y)[r].reshape(Hk, G, d), want[0]) < 2e-5
        assert rel(new[1, n], S) < 1e-6
    np.testing.assert_array_equal(new[0], pool[0])
    np.testing.assert_array_equal(new[1, [2, 4]], pool[1, [2, 4]])


def test_a_state_is_whole_device_tiles():
    """One (sequence, layer) state at the published widths: 8 heads of 128
    and their eight normalisers down the sublanes, 65 tiles of 128 lanes."""
    N, D = pr.state_rows(8, 128), pr.state_cols(128)
    assert (N, D) == (1032, 8320) and N % 8 == 0 and D % 128 == 0
    assert N * D * 4 == 34344960
