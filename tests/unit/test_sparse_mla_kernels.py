"""The selection kernels of ``ops/pallas/sparse_mla.py`` in interpret mode:
each against its XLA twin and against a float32 formula written here, at
contexts below, at and above ``topk``, across a page boundary, with ties."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import sparse_mla as sm

BS, D, HI = 16, 128, 4          # page size, index key width, index heads
W, V, H = 256, 128, 4           # latent row, its latent part, query heads
NB, MB = 40, 8                  # pages in the pool, pages a sequence


def _tables(rng, n):
    return jnp.asarray(np.stack([rng.permutation(NB - 1)[:MB] + 1
                                 for _ in range(n)]), jnp.int32)


def _index_inputs(seed, n, r):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((n, r, HI, D)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((n, r, HI)), jnp.float32)
    pool = jnp.asarray(rng.standard_normal((NB, BS, D)), jnp.bfloat16)
    return rng, q, w, pool, _tables(rng, n)


def _formula(q, w, pool, bt, q0, ctx):
    """I[t, s] = sum_j w_j relu(q_j . k_s), -inf where s is not seen."""
    n, r = q.shape[:2]
    keys = np.asarray(pool, np.float32)[np.asarray(bt)].reshape(n, -1, D)
    s = np.einsum("nrhd,nsd->nrhs", np.asarray(q, np.float32), keys)
    out = (np.maximum(s, 0) * np.asarray(w)[..., None]).sum(2)
    pos = np.arange(keys.shape[1])[None, None]
    seen = (pos < np.asarray(ctx)[:, None, None]) & (
        pos <= np.asarray(q0)[:, None, None] + np.arange(r)[None, :, None])
    return np.where(seen, out, -np.inf)


@pytest.mark.parametrize("rows,q0,ctx", [
    (1, [0, 17, 100, 127], [1, 18, 101, 128]),          # decode rows
    (16, [0, 16, 96, 0], [16, 32, 112, 0]),             # chunk slots, one empty
    (16, [5, 30], [21, 40]),                            # a slot partly filled
])
def test_index_scores(rows, q0, ctx):
    n = len(q0)
    _, q, w, pool, bt = _index_inputs(0, n, rows)
    q0, ctx = jnp.asarray(q0, jnp.int32), jnp.asarray(ctx, jnp.int32)
    got = sm.index_scores(q, w, pool, bt, q0, ctx)
    twin = sm.index_scores_reference(q, w, pool, bt, q0, ctx)
    assert got.shape == twin.shape and got.dtype == jnp.float32
    flat, want = np.asarray(sm.untile(got)), _formula(q, w, pool, bt, q0, ctx)
    np.testing.assert_array_equal(np.isfinite(flat), np.isfinite(want))
    live = np.isfinite(want)
    np.testing.assert_allclose(flat[live], want[live], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(sm.untile(twin))[live], want[live],
                               rtol=2e-5, atol=2e-5)


def _tiled(flat, tile):
    """[N, R, S] -> the kernels' [N, C, R, T]."""
    n, r, s = flat.shape
    return jnp.asarray(flat.reshape(n, r, s // tile, tile).transpose(
        0, 2, 1, 3))


def _scores(seed, n, r, ctx, ties):
    """Random scores of ``ctx`` finite positions a row (of 256), with runs
    of equal values where ``ties``."""
    rng = np.random.default_rng(seed)
    flat = rng.standard_normal((n, r, 256)).astype(np.float32)
    if ties:
        flat = np.round(flat * 2) / 2            # a dozen distinct values
    for i, c in enumerate(ctx):
        flat[i, :, c:] = -np.inf
    return flat


def _drawn(rows, tile, ties):
    """Six contexts below, at and above ``topk`` = 24, a slot each."""
    ctx = [1, 7, 24, 25, 130, 256]
    flat = _scores(1, len(ctx), rows, ctx, ties)
    k = np.broadcast_to(np.minimum(ctx, 24)[:, None], (len(ctx), rows))
    return dict(flat=flat, k=k, ctx=ctx, tile=tile, cut=ties, compact=True)


def _bits(x):
    return np.asarray(x, np.uint32).view(np.float32)


def _signed_zeros():
    """Scores of both signs, a quarter of them zeros of either sign. The
    threshold above the zeros, AT them (every zero kept, whichever its
    sign: a comparison holds them equal) and below them."""
    rng = np.random.default_rng(2)
    flat = rng.standard_normal((3, 16, 256)).astype(np.float32)
    zero = rng.random(flat.shape) < 0.25
    flat[zero] = np.where(rng.random(flat.shape) < 0.5, 0.0, -0.0)[zero]
    flat[2, 5] = -np.abs(flat[2, 5])       # a row whose largest is -0.0
    k = np.stack([(flat[0] > 0).sum(-1), (flat[1] >= 0).sum(-1),
                  (flat[2] >= 0).sum(-1) + 5])
    return dict(flat=flat, k=k, ctx=[256] * 3, tile=128, cut=False)


def _subnormals():
    """Subnormal scores of both signs (and the smallest of all) among
    ordinary ones; the threshold the smallest ordinary score above them,
    then the largest below them."""
    rng = np.random.default_rng(3)
    flat = rng.standard_normal((2, 16, 256)).astype(np.float32)
    tiny = rng.random(flat.shape) < 0.3
    small = _bits(rng.integers(1, 1 << 23, flat.shape)
                  | (rng.integers(0, 2, flat.shape) << 31))
    flat[tiny] = small[tiny]
    flat[:, :, 3], flat[:, :, 4] = _bits(1), _bits((1 << 31) | 1)
    k = np.stack([(flat[0] > 1e-30).sum(-1), (flat[1] > -1e-30).sum(-1) + 1])
    return dict(flat=flat, k=k, ctx=[256] * 2, tile=128, cut=False)


def _exponents():
    """Sixty powers of ten in one row, both signs, any k."""
    rng = np.random.default_rng(4)
    flat = (rng.choice([-1.0, 1.0], (4, 16, 256))
            * 10.0 ** rng.uniform(-30, 30, (4, 16, 256))).astype(np.float32)
    ctx = [256, 200, 129, 31]
    for i, c in enumerate(ctx):
        flat[i, :, c:] = -np.inf
    k = np.stack([rng.integers(1, c + 1, 16) for c in ctx])
    return dict(flat=flat, k=k, ctx=ctx, tile=128, cut=False)


def _one_value():
    """A row of ONE value: the bounds meet before any halving, and the
    count at the threshold is taken after it."""
    flat = np.full((3, 16, 256), -np.inf, np.float32)
    ctx = [256, 100, 9]
    for i, (c, v) in enumerate(zip(ctx, [0.5, -3.0, 0.0])):
        flat[i, :, :c] = v
    k = np.stack([np.full(16, 7), np.arange(1, 17) * 6, np.full(16, 9)])
    return dict(flat=flat, k=k, ctx=ctx, tile=128, cut=True)


def _k_extremes():
    """k = 1 and k = every finite score, row by row of one block."""
    ctx = [256, 130, 24, 1]
    flat = _scores(5, len(ctx), 16, ctx, False)
    k = np.stack([np.where(np.arange(16) % 2, c, 1) for c in ctx])
    return dict(flat=flat, k=k, ctx=ctx, tile=128, cut=False)


def _stops_differ():
    """Rows of ONE block that reach a candidate counting exactly k at
    different sweeps: row r's scores lie 2**-r apart around 1, around -1 and
    around 0."""
    rng = np.random.default_rng(6)
    steps = rng.permutation(256)[None, None, :].astype(np.float32) - 128
    flat = (np.asarray([1.0, -1.0, 0.0], np.float32)[:, None, None]
            + steps * 2.0 ** -np.arange(16, dtype=np.float32)[None, :, None])
    k = rng.integers(1, 257, (3, 16))
    return dict(flat=flat.astype(np.float32), k=k, ctx=[256] * 3, tile=128,
                cut=False)


def _crowded_beside_early():
    """Slot 0: a row whose ties outnumber its quota beside fifteen that stop
    early; slot 1: sixteen that stop early. The first block runs to the last
    bit and through the cut; the second takes fewer walks than a sweep a
    bit."""
    flat = _scores(7, 2, 16, [256, 256], False)
    flat[0, 3] = np.round(flat[0, 3])
    k = np.full((2, 16), 24)
    return dict(flat=flat, k=k, ctx=[256] * 2, tile=128, cut=True,
                crowded=[(0, 3)], walks=lambda w: (w[0, 0] >= 34 + 31
                                                   and w[1, 0] < 34))


def _tiles_and_unroll():
    """20 tiles of 128 walked 16 an iteration (``_select_block``): ``ext``
    of 1, one under the unroll (the iteration runs a tile past it: -inf
    alone), the unroll, one over it (a tile for the second loop) and C; the
    last slot keeps more than the 2,048 groups of the bounds' walk (no
    lower bound from them)."""
    assert sm._select_block(16, 20, 128) == (16, 16)
    ctx = [100, 15 * 128, 16 * 128 - 5, 16 * 128 + 1, 2560, 2560]
    rng = np.random.default_rng(8)
    flat = rng.standard_normal((len(ctx), 16, 2560)).astype(np.float32)
    for i, c in enumerate(ctx):
        flat[i, :, c:] = -np.inf
    k = np.stack([rng.integers(1, c + 1, 16) for c in ctx])
    k[-1] = rng.integers(2049, 2561, 16)
    return dict(flat=flat, k=k, ctx=ctx, tile=128, cut=False)


def _no_lower_bound():
    """Blocks whose lower bound is -inf: each row sees fewer scores than the
    bounds' walk has groups, and some of the groups are empty."""
    ctx = [40, 129, 255]
    flat = _scores(9, len(ctx), 32, ctx, False)
    k = np.stack([np.full(32, 24), np.full(32, 100), np.arange(1, 33) * 7])
    return dict(flat=flat, k=k, ctx=ctx, tile=128, cut=False)


SELECT_CASES = {
    **{f"{rows}-{tile}-{ties}": functools.partial(_drawn, rows, tile, ties)
       for ties in (False, True)
       for rows, tile in [(1, 256), (16, 128), (32, 128)]},
    "signed-zeros": _signed_zeros, "subnormals": _subnormals,
    "exponents": _exponents, "one-value": _one_value,
    "k-extremes": _k_extremes, "stops-differ": _stops_differ,
    "crowded-beside-early": _crowded_beside_early,
    "tiles-and-unroll": _tiles_and_unroll, "no-lower-bound": _no_lower_bound,
}


@pytest.mark.parametrize("case", sorted(SELECT_CASES))
def test_select_keeps_exactly_k_as_top_k_does(case):
    c = SELECT_CASES[case]()
    flat, ctx = c["flat"], c["ctx"]
    k = jnp.asarray(c["k"], jnp.int32)
    scores = _tiled(flat, c["tile"])
    cl = jnp.asarray(ctx, jnp.int32)
    thr, pcut, walks = sm.select_counted(scores, k, cl)
    thr_t, pcut_t = sm.select_reference(scores, k, cl)
    np.testing.assert_array_equal(np.asarray(thr), np.asarray(thr_t))
    np.testing.assert_array_equal(np.asarray(pcut), np.asarray(pcut_t))
    for got, same in zip(sm.select(scores, k, cl), (thr, pcut)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(same))
    keep = np.asarray(sm.keep_mask(scores, thr, pcut))
    # the formula: jax.lax.top_k on the float32 scores (equal scores: the
    # lower position first)
    _, idx = jax.lax.top_k(jnp.asarray(flat), int(k.max()))
    want = np.zeros_like(keep)
    for i in range(flat.shape[0]):
        for r in range(flat.shape[1]):
            want[i, r, np.asarray(idx[i, r, :int(k[i, r])])] = True
    np.testing.assert_array_equal(keep, want)
    cut = np.asarray(pcut) < 2**31 - 1
    assert cut.any() == c["cut"], "a row had a tie to cut, or none had"
    if "crowded" in c:
        assert sorted(zip(*np.nonzero(cut))) == c["crowded"]
    # a block of rows walks its tiles for the bounds, a halving at a time
    # and for the threshold; a sweep a bit was 1 + 32 + 2
    walks = np.asarray(walks)
    assert walks.shape == (flat.shape[0], flat.shape[1] // sm._select_block(
        flat.shape[1], scores.shape[1], c["tile"])[0])
    assert (walks >= 2).all() and (walks <= 2 + 32 + 1 + 32).all(), walks
    assert c.get("walks", lambda w: True)(walks), walks
    if not c.get("compact"):
        return
    # the decode rows' compaction of it
    topk = int(k.max())
    pos = np.asarray(sm.chosen_positions(jnp.asarray(keep[:, 0]), topk))
    for i in range(len(ctx)):
        chosen = np.flatnonzero(want[i, 0])
        np.testing.assert_array_equal(pos[i, :len(chosen)], chosen)
        assert (pos[i, len(chosen):] == 256).all()


def _latent_inputs(seed, n, rows):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((n, rows, W)) * 0.3, jnp.bfloat16)
    pool = jnp.asarray(rng.standard_normal((NB, BS, W)), jnp.bfloat16)
    return rng, q, pool, _tables(rng, n)


def _softmax_over(q, rows, mask, scale):
    s = np.einsum("nrw,ntw->nrt", np.asarray(q, np.float32), rows) * scale
    s = np.where(mask, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True, initial=-1e30))
    p = np.where(mask, p, 0.0)
    l = p.sum(-1, keepdims=True)
    return np.einsum("nrt,ntv->nrv", p / np.where(l > 0, l, 1), rows[..., :V])


@pytest.mark.parametrize("side", [False, True])
def test_attend_decode(side):
    n, K = 4, 24
    rng, q, _, _ = _latent_inputs(2, n, H)
    rows = jnp.asarray(rng.standard_normal((n, K, W)), jnp.bfloat16)
    n_rows = jnp.asarray([0 if side else 1, 7, 23, 24], jnp.int32)
    kw = dict(v_dim=V, softmax_scale=0.1)
    if side:
        kw["side"] = jnp.asarray(rng.standard_normal((n, 8, W)), jnp.bfloat16)
        kw["side_on"] = jnp.asarray([1, 0, 1, 1], jnp.int32)
    got = sm.attend_decode(q, rows, n_rows, **kw)
    twin = sm.attend_decode_reference(q, rows, n_rows, **kw)
    g = np.asarray(rows, np.float32)
    mask = np.arange(K)[None] < np.asarray(n_rows)[:, None]
    if side:
        g = np.concatenate([g, np.asarray(kw["side"], np.float32)[:, :1]], 1)
        mask = np.concatenate(
            [mask, np.asarray(kw["side_on"])[:, None] > 0], 1)
    want = _softmax_over(q, g, np.broadcast_to(mask[:, None], (n, H, K + side)),
                         0.1)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=2e-2)
    np.testing.assert_allclose(np.asarray(twin, np.float32), want, atol=2e-2)


@pytest.mark.parametrize("ties", [False, True])
def test_attend_chunk_attends_what_the_selection_keeps(ties):
    """Index, select, attend as a paged pass runs them: three slots of 16
    query tokens at contexts below, across and above ``topk`` (the slots'
    pages span page boundaries), one empty."""
    n, cs, topk = 4, 16, 24
    q0 = jnp.asarray([0, 16, 96, 0], jnp.int32)
    ctx = jnp.asarray([16, 32, 112, 0], jnp.int32)
    rng, qi, w, ipool, bt = _index_inputs(3, n, cs)
    if ties:       # few distinct keys: many equal scores
        ipool = jnp.asarray(rng.integers(0, 2, (NB, 1, D)) * np.ones(
            (1, BS, 1)), jnp.bfloat16)
    _, q, pool, _ = _latent_inputs(4, n, cs * H)
    scores = sm.index_scores(qi, w, ipool, bt, q0, ctx)
    seen = jnp.minimum(ctx[:, None], q0[:, None] + jnp.arange(cs) + 1)
    k = jnp.clip(jnp.minimum(seen, topk), 1)
    thr, pcut = sm.select(scores, k, ctx)
    kw = dict(heads=H, v_dim=V, softmax_scale=0.1)
    got = sm.attend_chunk(q, pool, bt, q0, ctx, scores, thr, pcut, **kw)
    twin = sm.attend_chunk_reference(q, pool, bt, q0, ctx, scores, thr, pcut,
                                     **kw)
    # the formula: top_k of the float32 scores a query token
    flat = np.asarray(sm.untile(scores))
    _, idx = jax.lax.top_k(jnp.asarray(flat), topk)
    mask = np.zeros(flat.shape, bool)
    for i in range(n):
        for r in range(cs):
            mask[i, r, np.asarray(idx[i, r, :int(k[i, r])])] = True
    mask &= np.isfinite(flat)
    assert mask.sum(-1).max() == topk and (mask.sum(-1)[3] == 0).all()
    rows = np.asarray(pool, np.float32)[np.asarray(bt)].reshape(n, -1, W)
    want = _softmax_over(q, rows, np.repeat(mask, H, axis=1), 0.1)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=2e-2)
    np.testing.assert_allclose(np.asarray(twin, np.float32), want, atol=2e-2)


def _expansion(rng, heads, rank, nope, rope, v):
    """``w_uk`` [H, rank, nope], ``w_uv`` [H, rank, v] and the map from a
    latent ROW to a head's key and value that ``attend_expanded`` takes:
    ``W_UK`` and ``W_UV`` over the latent part, the identity from the row's
    rotary key to the key's last ``rope`` values."""
    w_uk = rng.standard_normal((heads, rank, nope)).astype(np.float32) * 0.2
    w_uv = rng.standard_normal((heads, rank, v)).astype(np.float32) * 0.2
    w_kv = np.zeros((heads, W, nope + rope + v), np.float32)
    w_kv[:, :rank, :nope] = w_uk
    w_kv[:, rank:rank + rope, nope:nope + rope] = np.eye(rope)
    w_kv[:, :rank, nope + rope:] = w_uv
    return w_uk, w_uv, jnp.asarray(w_kv, jnp.bfloat16)


#: (nope, rope, v) of a head: the value as wide as the key's nope part, and
#: wider (GLM-5: 192 + 64 and 256)
EXPANDED_WIDTHS = {"v_is_nope": (64, 64, 64), "v_wider": (32, 32, 128)}


@pytest.mark.parametrize("widths", list(EXPANDED_WIDTHS))
@pytest.mark.parametrize("ties", [False, True])
def test_attend_expanded_is_attend_chunk_of_one_sequence(ties, widths):
    """Four slots of 16 query tokens that are ONE sequence's (one block
    table, consecutive positions: contexts below, at and above ``topk``, the
    third slot partly filled, the fourth empty) through the expanded kernel
    and through the absorbed one: the same attention, ``W_UK`` in the keys
    or in the queries, ``W_UV`` in the values or on the output."""
    nope, rope, v = EXPANDED_WIDTHS[widths]
    n, cs, topk, rank = 4, 16, 24, V
    q0 = jnp.asarray([8, 24, 40, 0], jnp.int32)
    ctx = jnp.asarray([24, 40, 51, 0], jnp.int32)
    rng, qi, w, ipool, bt = _index_inputs(5, 1, cs)
    bt = jnp.broadcast_to(bt, (n, MB))
    qi = jnp.asarray(rng.standard_normal((n, cs, HI, D)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((n, cs, HI)), jnp.float32)
    if ties:       # few distinct keys: many equal scores, ``pcut`` decides
        ipool = jnp.asarray(rng.integers(0, 2, (NB, 1, D)) * np.ones(
            (1, BS, 1)), jnp.bfloat16)
    pool = np.zeros((NB, BS, W), np.float32)
    pool[..., :rank + rope] = rng.standard_normal((NB, BS, rank + rope))
    pool = jnp.asarray(pool, jnp.bfloat16)
    w_uk, w_uv, w_kv = _expansion(rng, H, rank, nope, rope, v)
    q = jnp.asarray(rng.standard_normal((n, H, cs, nope + rope)) * 0.5,
                    jnp.bfloat16)
    scores = sm.index_scores(qi, w, ipool, bt, q0, ctx)
    seen = jnp.minimum(ctx[:, None], q0[:, None] + jnp.arange(cs) + 1)
    thr, pcut = sm.select(scores, jnp.clip(jnp.minimum(seen, topk), 1), ctx)
    if ties:
        assert (np.asarray(pcut)[:3] < 2**31 - 1).any(), "no tie to cut"
    kw = dict(k_dim=nope + rope, softmax_scale=0.1)
    got = sm.attend_expanded(q, w_kv, pool, bt[0], ctx, scores, thr, pcut,
                             **kw)
    twin = sm.attend_expanded_reference(q, w_kv, pool, bt[0], ctx, scores,
                                        thr, pcut, **kw)
    assert got.shape == (n, cs, H * v) and got.dtype == q.dtype
    # the absorbed form in float32 on the same (bfloat16) values
    qf = np.asarray(q, np.float32)
    uk = np.asarray(w_kv, np.float32)[:, :rank, :nope]
    q_abs = np.zeros((n, cs, H, W), np.float32)
    q_abs[..., :rank] = np.einsum("nhcd,hrd->nchr", qf[..., :nope], uk)
    q_abs[..., rank:rank + rope] = qf[..., nope:].transpose(0, 2, 1, 3)
    o_lat = sm.attend_chunk_reference(
        jnp.asarray(q_abs.reshape(n, cs * H, W)), pool, bt, q0, ctx, scores,
        thr, pcut, heads=H, v_dim=rank, softmax_scale=0.1)
    want = np.einsum("nchr,hrv->nchv", np.asarray(o_lat).reshape(
        n, cs, H, rank), np.asarray(w_kv, np.float32)[:, :rank, nope + rope:]
    ).reshape(n, cs, H * v)
    assert np.abs(want[:3]).max() > 0.5 and (want[3] == 0).all()
    # the third slot's rows past its 11 tokens see what its last token does
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=3e-2)
    np.testing.assert_allclose(np.asarray(twin, np.float32), want, atol=3e-2)
