"""The selection kernels of ``ops/pallas/sparse_mla.py`` in interpret mode:
each against its XLA twin and against a float32 formula written here, at
contexts below, at and above ``topk``, across a page boundary, with ties."""

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


@pytest.mark.parametrize("ties", [False, True])
@pytest.mark.parametrize("rows,tile", [(1, 256), (16, 128), (32, 128)])
def test_select_keeps_exactly_k_as_top_k_does(rows, tile, ties):
    ctx = [1, 7, 24, 25, 130, 256]
    topk = 24
    flat = _scores(1, len(ctx), rows, ctx, ties)
    scores = _tiled(flat, tile)
    k = jnp.broadcast_to(jnp.minimum(jnp.asarray(ctx), topk)[:, None],
                         (len(ctx), rows)).astype(jnp.int32)
    cl = jnp.asarray(ctx, jnp.int32)
    thr, pcut = sm.select(scores, k, cl)
    thr_t, pcut_t = sm.select_reference(scores, k, cl)
    np.testing.assert_array_equal(np.asarray(thr), np.asarray(thr_t))
    np.testing.assert_array_equal(np.asarray(pcut), np.asarray(pcut_t))
    keep = np.asarray(sm.keep_mask(scores, thr, pcut))
    # the formula: jax.lax.top_k on the float32 scores (equal scores: the
    # lower position first)
    _, idx = jax.lax.top_k(jnp.asarray(flat), topk)
    want = np.zeros_like(keep)
    for i, c in enumerate(ctx):
        for r in range(rows):
            want[i, r, np.asarray(idx[i, r, :min(c, topk)])] = True
    np.testing.assert_array_equal(keep, want)
    if ties:
        assert (np.asarray(pcut) < 2**31 - 1).any(), "no row had a tie to cut"
    # the decode rows' compaction of it
    pos = np.asarray(sm.chosen_positions(jnp.asarray(keep[:, 0]), topk))
    for i, c in enumerate(ctx):
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
