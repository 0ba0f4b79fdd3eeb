"""The gated delta-rule kernels (``ops/pallas/gdn.py``) against the
sequential recurrence written out in numpy: the chunked scan (the Pallas
kernel through the interpreter, and its XLA twin) over ragged lengths,
continued across calls, at the edges of ``beta`` and of the decay, one, two
and four value heads a key head (packed into one inverse chain where they fit
a tile), a chunk of zeros taking the short way; the MXU passes a grid step
issues, counted in the kernel's jaxpr; the one-token step against one step of
the recurrence, the dump slot untouched."""

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
DTYPES = [jnp.float32, jnp.bfloat16]


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


@pytest.mark.parametrize("chunk,Hk,Hv", [
    (64, 2, 4), (128, 2, 4), (16, 2, 4),       # two heads a key head
    (64, 1, 1), (128, 1, 1),                   # one: nothing to pack
    (64, 1, 4), (16, 1, 4), (16, 1, 8)],       # four: two chains of two; one
    ids=lambda x: str(x))                      # of four; one of eight
def test_any_chunk_is_the_same_recurrence(chunk, Hk, Hv):
    """The chunk and the value heads a key head serves varied: neither the
    chunk nor how many heads share an inverse chain is part of the
    mathematics."""
    rng = np.random.default_rng(chunk + Hv)
    Cs = 128
    q, k, v, g, beta = draw(rng, Cs, Hk, Hv)
    want, S = recurrence(q, k, v, g, beta, np.zeros((N, Hv * P)))
    y, hT = gdn.gdn_chunk_scan(flat(q), flat(k), flat(v), jnp.asarray(g),
                               jnp.asarray(beta),
                               jnp.zeros((1, N, Hv * P)), jnp.zeros((1,), int),
                               chunk=chunk)
    assert rel(y, want) < 2e-5 and rel(hT[0], S) < 2e-5


def as_dtype(x, dtype):
    """``x`` ``[T, ..]`` as the kernel's ``[T, -1]`` argument of ``dtype``,
    and the values it then holds, in ``x``'s shape."""
    arg = jnp.asarray(x.reshape(x.shape[0], -1), dtype)
    return arg, np.asarray(arg.astype(jnp.float32)).reshape(x.shape)


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_a_sequence_resumes_across_calls(dtype):
    """A call's last state handed to the next call as ``h0`` gives what one
    call over all the tokens gives; inside a call the second slot goes on
    from the first (``cont``)."""
    rng = np.random.default_rng(11)
    Hk, Hv, Cs = 1, 2, 64
    q, k, v, g, beta = draw(rng, 4 * Cs, Hk, Hv)
    (qa, q), (ka, k), (va, v) = (as_dtype(x, dtype) for x in (q, k, v))
    want, S = recurrence(q, k, v, g, beta, np.zeros((N, Hv * P)))
    h = jnp.zeros((N, Hv * P))
    ys = []
    for i in range(2):
        part = slice(2 * i * Cs, 2 * (i + 1) * Cs)
        y, hT = gdn.gdn_chunk_scan(
            qa[part], ka[part], va[part], jnp.asarray(g[part]),
            jnp.asarray(beta[part]), jnp.stack([h, jnp.ones_like(h)]),
            jnp.asarray([0, 1]))
        h = hT[1]
        ys.append(y)
    assert rel(jnp.concatenate(ys), want) < 2e-5 and rel(h, S) < 2e-5


@pytest.mark.parametrize("Hv", [2, 1, 4])
def test_repeated_keys_do_not_cancel(Hv):
    """The same key written 128 times with beta 1 and no decay: the powers of
    ``A`` a Neumann series would sum reach 1e37, block substitution stays at
    rounding."""
    rng = np.random.default_rng(5)
    q, k, v, g, beta = draw(rng, 128, 1, Hv, beta=1.0, decay=1.0)
    k[:] = k[0]
    want, S = recurrence(q, k, v, g, beta, np.zeros((N, Hv * P)))
    y, hT = gdn.gdn_chunk_scan(flat(q), flat(k), flat(v), jnp.asarray(g),
                               jnp.asarray(beta), jnp.zeros((1, N, Hv * P)),
                               jnp.zeros((1,), int))
    assert rel(y, want) < 1e-4 and rel(hT[0], S) < 1e-4


@pytest.mark.parametrize("Hv", [2, 1, 4])
def test_bfloat16_activations_take_the_one_pass_products(Hv):
    """q, k and v as the engine hands them (bfloat16 values): the scan still
    follows the recurrence on those values, from a state that is not zero
    (the three-pass products ``[K; Q] S_0`` and ``K^T (d W)`` against it)."""
    rng = np.random.default_rng(9)
    q, k, v, g, beta = draw(rng, 64, 1, Hv)
    (qa, q), (ka, k), (va, v) = (as_dtype(x, jnp.bfloat16) for x in (q, k, v))
    S0 = rng.standard_normal((N, Hv * P)).astype(np.float32)
    want, S = recurrence(q, k, v, g, beta, S0)
    y, hT = gdn.gdn_chunk_scan(qa, ka, va, jnp.asarray(g), jnp.asarray(beta),
                               jnp.asarray(S0)[None], jnp.zeros((1,), int))
    assert rel(y, want) < 2e-5 and rel(hT[0], S) < 2e-5


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_a_slow_head_beside_a_fast_one(dtype):
    """The two value heads of a key head share one inverse chain: one that
    forgets nothing in a chunk (``exp(g)`` 0.98-1, as the configuration's
    slow heads) beside one whose decay underflows within it. Nothing of
    either reaches the other's block."""
    rng = np.random.default_rng(13)
    q, k, v, g, beta = draw(rng, 128, 1, 2)
    g[:, 0] = np.log(rng.uniform(0.98, 1.0, 128))
    g[:, 1] = np.log(rng.uniform(1e-4, 5e-2, 128))      # sums to -300 .. -600
    (qa, q), (ka, k), (va, v) = (as_dtype(x, dtype) for x in (q, k, v))
    S0 = rng.standard_normal((N, 2 * P)).astype(np.float32)
    want, S = recurrence(q, k, v, g, beta, S0)
    y, hT = gdn.gdn_chunk_scan(qa, ka, va, jnp.asarray(g), jnp.asarray(beta),
                               jnp.asarray(S0)[None], jnp.zeros((1,), int))
    y, hT = np.asarray(y), np.asarray(hT)
    assert np.isfinite(y).all() and np.isfinite(hT).all()
    for h in range(2):                  # each head against its own scale
        lanes = slice(h * P, (h + 1) * P)
        assert rel(y[:, lanes], want[:, lanes]) < 2e-5
        assert rel(hT[0][:, lanes], S[:, lanes]) < 2e-5


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("where", ["inside_a_slot", "a_whole_slot"])
def test_a_chunk_of_zeros_writes_nothing(where, dtype):
    """A chunk whose ``g`` and ``beta`` are all zero between two live ones,
    its q, k and v NOT zero: ``o = S^T q`` on its rows and the state goes
    through it untouched, bit for bit. Inside a slot of three chunks, and as
    a whole slot between two that it joins (``cont`` 1, then 2)."""
    rng = np.random.default_rng(17)
    Hk, Hv, Q = 1, 2, 64
    q, k, v, g, beta = draw(rng, 3 * Q, Hk, Hv)
    g[Q:2 * Q], beta[Q:2 * Q] = 0.0, 0.0
    (qa, q), (ka, k), (va, v) = (as_dtype(x, dtype) for x in (q, k, v))
    S0 = rng.standard_normal((N, Hv * P)).astype(np.float32)
    want, S = recurrence(q, k, v, g, beta, S0)
    G = 1 if where == "inside_a_slot" else 3
    h0 = jnp.concatenate([jnp.asarray(S0)[None], jnp.ones((G - 1, N, Hv * P))])
    cont = jnp.asarray([0, 1, 2][:G])
    y, hT = gdn.gdn_chunk_scan(qa, ka, va, jnp.asarray(g), jnp.asarray(beta),
                               h0, cont)
    assert rel(y, want) < 2e-5 and rel(hT[-1], S) < 2e-5
    if G == 3:
        # the slot of zeros hands on the bits it was handed, and reads them
        carried = np.asarray(hT[0], np.float64).reshape(N, Hv, P)
        read = np.einsum("nhp,tn->thp", carried, q[Q:2 * Q, 0])
        assert rel(y[Q:2 * Q], read.reshape(Q, Hv * P)) < 2e-5
        assert (np.asarray(hT[1]) == np.asarray(hT[0])).all()
    else:
        # the same tokens without the chunk of zeros end in the same bits
        live = np.r_[0:Q, 2 * Q:3 * Q]
        _, h2 = gdn.gdn_chunk_scan(qa[live], ka[live], va[live],
                                   jnp.asarray(g[live]),
                                   jnp.asarray(beta[live]), h0, cont)
        assert (np.asarray(h2) == np.asarray(hT)).all()


def _passes(jaxpr):
    """MXU passes of the products in ``jaxpr`` and whatever it calls: six for
    a ``dot_general`` at the highest precision, one for any other; and how
    many of the products are at the highest."""
    total = highest = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            top = "HIGHEST" in str(eqn.params["precision"])
            total, highest = total + (6 if top else 1), highest + top
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n, h = _passes(sub)
            total, highest = total + n, highest + h
    return total, highest


def _kernel_of(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return eqn.params["jaxpr"]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found = _kernel_of(sub)
            if found is not None:
                return found


@pytest.mark.parametrize("dtype,most", [(jnp.bfloat16, 90), (jnp.float32, 192)],
                         ids=["bfloat16", "float32"])
def test_a_grid_step_issues_the_passes_that_change_the_result(dtype, most):
    """The kernel's jaxpr at one key head serving two value heads, chunks of
    64. PR 47's step held 2 + 2 x 15 x 6 = 182 passes at bfloat16 activations
    (192 at float32). Now: ``K K^T``, ``Q K^T`` 2; ONE chain of ten products
    for both heads 60; ``inv . rhs`` and ``(M o Q K^T) W`` 12; ``[K; Q] S_0``
    and ``K^T (d W)`` three each where the activations are bfloat16, six
    where they are not. The branch of a chunk of zeros holds ``Q S_0`` alone,
    and at bfloat16 no product at the highest precision. A shape that fell
    back to a chain a head, or to six-pass products, shows here."""
    T, Hk, Hv = 128, 1, 2
    args = (jnp.zeros((T, Hk * N), dtype), jnp.zeros((T, Hk * N), dtype),
            jnp.zeros((T, Hv * P), dtype), jnp.zeros((T, Hv)),
            jnp.zeros((T, Hv)), jnp.zeros((1, N, Hv * P)),
            jnp.zeros((1,), jnp.int32))
    kernel = _kernel_of(jax.make_jaxpr(gdn.gdn_chunk_scan)(*args).jaxpr)
    branches = []                       # the ``pl.when``s that hold products
    for eqn in kernel.eqns:
        assert eqn.primitive.name != "dot_general", "a product on every step"
        if eqn.primitive.name == "cond":
            counts = [_passes(b.jaxpr) for b in eqn.params["branches"]]
            branches += [c for c in counts if c[0]]
    (short, short_top), (full, full_top) = sorted(branches)
    bf16 = dtype == jnp.bfloat16
    assert full == (80 if bf16 else 96) and full <= most
    assert full_top == (12 if bf16 else 16)
    assert (short, short_top) == ((3, 0) if bf16 else (6, 1))


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
