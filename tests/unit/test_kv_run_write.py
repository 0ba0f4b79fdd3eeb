"""A run of K/V rows written as a RUN (``paged_attention.paged_kv_run_write``,
``ragged_model._kv_run_write``; PR 62): the pool it leaves is the row
scatter's (``ragged_model._kv_page_write``) BIT FOR BIT, every slot of every
page compared — what lies beside a run keeps what it held — on drawn plans of
the two programs that call it: a paged pass's chunk slots and a block step's
blocks. The kernels run in the Pallas interpreter here; that the chip's
compiler takes them at real widths is ``test_chip_compile.py``'s to say.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import ragged_model as rm
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.ops.pallas import paged_attention as pa

L, NB, MB = 2, 24, 12
SCRATCH = NB - 1


def _pool(rng, hkv, bs, d, dtype):
    return jnp.asarray(rng.standard_normal((L, NB, 2, hkv, bs, d)), dtype)


def _rows(rng, t, hkv, d, dtype):
    return (jnp.asarray(rng.standard_normal((t, hkv, d)), dtype),
            jnp.asarray(rng.standard_normal((t, hkv, d)), dtype))


def _dest(tables, pos0, count, n, bs):
    """The scatter's destinations of the runs' rows: ``page * bs + slot``,
    the cache's sentinel for a row past its run's count."""
    i = np.arange(n)[None]
    pos = pos0[:, None] + i
    page = np.take_along_axis(tables, np.minimum(pos // bs, MB - 1), axis=1)
    return np.where(i < count[:, None], page * bs + pos % bs,
                    NB * bs).reshape(-1).astype(np.int32)


@functools.partial(jax.jit, static_argnames=("n", "aligned", "group"))
def _written(pool, k, v, tables, pos0, count, dest, layer, *, n, aligned,
             group):
    """``(the run writer's pool, the row scatter's)``, compiled once a shape
    (the interpreted kernel is slow op by op)."""
    _, _, _, hkv, bs, d = pool.shape
    want = rm._kv_page_write(pool.reshape(-1, d), k, v,
                             rm._layer_dest(dest, layer, NB, bs, L), hkv, bs)
    if group is None:
        plan = rm._run_write_plan(pool, tables, pos0, count, n, 1, aligned)
    else:
        plan = pa.kv_run_plan(tables, pos0, count, n, group, bs, aligned)
    assert plan is not None
    got = rm._kv_run_write(pool.reshape(-1, d), k, v, layer, plan, pool.shape)
    return got.reshape(pool.shape), want.reshape(pool.shape)


def _both(pool, k, v, tables, pos0, count, n, layer=1, aligned=False,
          group=None):
    """``(the run writer's pool, the row scatter's)`` for layer ``layer``."""
    tables, pos0, count = (np.asarray(a, np.int32)
                           for a in (tables, pos0, count))
    dest = _dest(tables, pos0, count, n, pool.shape[4])
    return _written(pool, k, v, tables, pos0, count, dest, np.int32(layer),
                    n=n, aligned=aligned, group=group)


def _tables(rng, seqs, pages_each):
    """A table a sequence over pages of its own (page 0 and the scratch
    page are nobody's)."""
    free = rng.permutation(np.arange(1, NB - 1))
    t = np.zeros((seqs, MB), np.int32)
    for s in range(seqs):
        t[s, :pages_each] = free[s * pages_each:(s + 1) * pages_each]
    return t


#: a paged pass's plans at 4 slots of 32 rows over pages of 32 (tiles of 16
#: in bfloat16): name -> (sequence of each slot, first position, rows)
_PASS_PLANS = {
    # a page's start, whole pages
    "from_a_pages_start": ([0, 1, 2, 3], [0, 32, 64, 96], [32, 32, 32, 32]),
    # from inside a tile, ending inside another
    "from_inside_a_tile": ([0, 1, 2, 3], [5, 21, 43, 1], [32, 32, 32, 32]),
    "ending_inside_a_tile": ([0, 1, 2, 3], [0, 16, 32, 48], [7, 17, 31, 1]),
    # every run crosses a page boundary, where the table turns
    "across_a_page_boundary": ([0, 1, 2, 3], [16, 31, 17, 50],
                               [32, 32, 20, 32]),
    # a sequence's slots in a row: the second begins in the tile the first
    # ends in, and the last is short
    "two_slots_of_one_sequence": ([0, 0, 1, 1], [9, 41, 64, 96],
                                  [32, 32, 32, 11]),
    "three_slots_and_a_short_last": ([0, 0, 0, 1], [23, 55, 87, 3],
                                     [32, 32, 5, 32]),
    "a_short_last_slot": ([0, 1, 2, 3], [0, 7, 90, 33], [32, 3, 1, 30]),
    # slots that hold nothing: their table is zeros, page 0 — beside a slot
    # that holds rows, in front of one, and alone
    "padding_slots": ([0, 1, 2, 3], [13, 40, 0, 0], [32, 9, 0, 0]),
    "padding_in_front": ([0, 1, 2, 3], [0, 0, 77, 3], [0, 0, 32, 32]),
    "nothing_but_padding": ([0, 1, 2, 3], [0, 0, 0, 0], [0, 0, 0, 0]),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("plan", sorted(_PASS_PLANS))
def test_a_pass_s_runs_leave_the_scatter_s_pool(plan, dtype):
    rng = np.random.default_rng(sorted(_PASS_PLANS).index(plan))
    seq, pos0, count = (np.asarray(a) for a in _PASS_PLANS[plan])
    n, bs = 32, 32
    tables = _tables(rng, 4, 5)[seq]
    tables[count == 0] = 0          # an empty slot's table, as the batch's
    pool = _pool(rng, 2, bs, 128, dtype)
    k, v = _rows(rng, 4 * n, 2, 128, dtype)
    got, want = _both(pool, k, v, tables, pos0, count, n)
    assert bool(jnp.array_equal(got, want))
    assert bool(jnp.array_equal(got[0], pool[0])), "another layer's pages"


@pytest.mark.parametrize("group", [16, 32, 64])
def test_a_run_is_the_same_at_every_group_of_slots(group):
    """What ``kv_run_group`` picks is a cost, not a result."""
    rng = np.random.default_rng(group)
    n, bs = 64, 64
    tables = _tables(rng, 2, 6)[[0, 0, 1, 1]]
    pos0, count = np.array([27, 91, 100, 164]), np.array([64, 64, 64, 19])
    pool = _pool(rng, 2, bs, 128, jnp.bfloat16)
    k, v = _rows(rng, 4 * n, 2, 128, jnp.bfloat16)
    got, want = _both(pool, k, v, tables, pos0, count, n, group=group)
    assert bool(jnp.array_equal(got, want))


@pytest.mark.parametrize("hkv,d", [(1, 128), (2, 128), (4, 128), (8, 128),
                                   (1, 256), (2, 256)])
def test_runs_of_every_head_count_and_width(hkv, d):
    rng = np.random.default_rng(10 * hkv + d)
    n, bs = 16, 16
    tables = _tables(rng, 3, 4)[[0, 0, 1, 2]]
    pos0, count = np.array([11, 27, 32, 0]), np.array([16, 16, 9, 0])
    pool = _pool(rng, hkv, bs, d, jnp.bfloat16)
    k, v = _rows(rng, 4 * n, hkv, d, jnp.bfloat16)
    got, want = _both(pool, k, v, tables, pos0, count, n, layer=0)
    assert bool(jnp.array_equal(got, want))
    # and a block step's blocks at the same widths
    k, v = _rows(rng, 3 * 4, hkv, d, jnp.bfloat16)
    got, want = _both(pool, k, v, _tables(rng, 3, 4), np.array([28, 48, 4]),
                      np.full(3, 4), 4, aligned=True)
    assert bool(jnp.array_equal(got, want))


def test_a_ring_reused_page_keeps_its_live_slots():
    """A windowed sequence's logical pages go round a ring of 3: the run
    lands on a page whose other slots hold the window's live rows (here:
    whatever the drawn pool held), and those stay."""
    rng = np.random.default_rng(5)
    n, bs = 32, 32
    ring = np.array([3, 9, 14])
    tables = np.stack([ring[np.arange(MB) % 3]] * 2).astype(np.int32)
    # positions 230..293: logical pages 7, 8, 9 -> ring pages 9, 14, 3
    pos0, count = np.array([230, 262]), np.array([32, 32])
    pool = _pool(rng, 2, bs, 128, jnp.bfloat16)
    k, v = _rows(rng, 2 * n, 2, 128, jnp.bfloat16)
    got, want = _both(pool, k, v, tables, pos0, count, n)
    assert bool(jnp.array_equal(got, want))
    untouched = np.asarray(got[1, 9, 0, 0, :230 % bs] == pool[1, 9, 0, 0,
                                                              :230 % bs])
    assert untouched.all()


@pytest.mark.parametrize("aligned", [True, False], ids=["aligned", "any"])
@pytest.mark.parametrize("offset", [0, 4, 8, 12])
def test_a_block_of_four_at_every_offset_of_a_tile(offset, aligned):
    """``B`` = 4 rows in a tile of 16 slots, each sequence on pages of its
    own: at every place a block can have in its tile, in the first tile of a
    page and in its last."""
    rng = np.random.default_rng(offset)
    bs, S = 32, 4
    tables = _tables(rng, S, 3)
    ctx0 = np.array([offset, 16 + offset, 32 + offset, 80 + offset])
    pool = _pool(rng, 4, bs, 128, jnp.bfloat16)
    k, v = _rows(rng, S * 4, 4, 128, jnp.bfloat16)
    got, want = _both(pool, k, v, tables, ctx0, np.full(S, 4), 4,
                      aligned=aligned)
    assert bool(jnp.array_equal(got, want))


@pytest.mark.parametrize("start", [1, 6, 13, 15])
def test_a_short_run_from_any_position(start):
    """Runs shorter than a tile that start anywhere (``aligned`` not
    promised): one may straddle two tiles, or two pages."""
    rng = np.random.default_rng(start)
    bs, S = 16, 3
    tables = _tables(rng, S, 3)
    pos0 = np.array([start, 16 + start, 32 - start])
    pool = _pool(rng, 2, bs, 128, jnp.bfloat16)
    k, v = _rows(rng, S * 4, 2, 128, jnp.bfloat16)
    got, want = _both(pool, k, v, tables, pos0, np.array([4, 4, 3]), 4)
    assert bool(jnp.array_equal(got, want))


def test_pad_rows_write_the_scratch_page_only():
    """A block step's pad rows hold tables that are all the scratch page, at
    position 0: every page but that one is the scatter's, and on it the rows
    written are SOME pad row's (the scatter promises no more)."""
    rng = np.random.default_rng(9)
    bs, S = 32, 6
    tables = _tables(rng, S, 3)
    tables[2:] = SCRATCH
    ctx0 = np.array([20, 64, 0, 0, 0, 0])
    pool = _pool(rng, 2, bs, 128, jnp.bfloat16)
    k, v = _rows(rng, S * 4, 2, 128, jnp.bfloat16)
    got, want = _both(pool, k, v, tables, ctx0, np.full(S, 4), 4,
                      aligned=True)
    real = np.arange(NB) != SCRATCH
    assert bool(jnp.array_equal(got[:, real], want[:, real]))
    assert bool(jnp.array_equal(got[0], pool[0]))
    page = np.asarray(got[1, SCRATCH].astype(jnp.float32))
    assert np.array_equal(page[..., 4:, :],
                          np.asarray(pool[1, SCRATCH, ..., 4:, :]
                                     .astype(jnp.float32)))
    pads = np.asarray(k.astype(jnp.float32)).reshape(S, 4, 2, 128)[2:]
    assert any(np.array_equal(page[0, :, :4], np.moveaxis(p, 0, 1))
               for p in pads)


@pytest.mark.parametrize("shape,n,group", [
    ((1, 2, 4, 128, 128), 256, 128),     # cell 15's paged pass
    ((1, 2, 8, 128, 128), 256, 128),     # Mistral's
    ((1, 2, 2, 128, 256), 256, 128),     # heads of 256
    ((1, 2, 4, 128, 128), 4, 16),        # a block of 4: one tile
    ((1, 2, 2, 16, 128), 16, 16),        # a tile a page
    ((1, 2, 2, 128, 64), 256, None),     # no whole lane tile a head
    ((1, 2, 2, 8, 128), 16, None),       # a page under a tile
    ((1, 2, 2, 128, 128), 24, None),     # a run of a tile and a half
])
def test_the_group_is_read_off_the_shapes(shape, n, group):
    pool = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    assert pa.kv_run_group(pool, n) == group


def test_the_plan_repeats_a_step_for_a_run_that_holds_nothing():
    """An empty run's steps are the step before them again (no fetch, no
    write: its table's page 0 may be another run's), and the steps in front
    of the first run that holds rows are that run's first. Every array is
    whole tiles of 128 (a shorter scalar operand reached the chip's kernel
    wrong)."""
    tables = jnp.asarray([[0] * 4, [5, 6, 7, 8], [0] * 4, [9, 10, 11, 12]])
    plan = pa.kv_run_plan(tables, jnp.asarray([0, 40, 0, 7]),
                          jnp.asarray([0, 20, 0, 3]), 32, 16, 32)
    steps = plan.steps
    assert (plan.n, plan.group, steps) == (32, 16, 12)
    assert all(a.shape == (128,) for a in plan[3:])
    head = lambda a: a[:steps].tolist()
    assert head(plan.run) == [1] * 9 + [3] * 3
    assert head(plan.base) == [32] * 3 + [32, 48, 48] + [48] * 3 + [0] * 3
    assert head(plan.page) == [6] * 9 + [9] * 3
    assert head(plan.slot) == [0] * 3 + [0, 1, 1] + [1] * 3 + [0] * 3
    assert head(plan.lo) == [40] * 9 + [7] * 3
    assert head(plan.hi) == [60] * 9 + [10] * 3


def test_the_plan_lets_chained_chunks_lay_each_other_s_rows():
    """Two chunk slots of one sequence in a row, the second from inside the
    group the first ends in: the steps on that group lay both runs' rows."""
    tables = jnp.asarray([[3, 4, 5, 6]] * 2 + [[7, 8, 9, 10]])
    plan = pa.kv_run_plan(tables, jnp.asarray([9, 41, 41]),
                          jnp.asarray([32, 20, 32]), 32, 16, 32)
    steps = pa._run_steps(32, 16, False)
    assert plan.lo[:3 * steps].tolist() == [9] * 3 + [9] * 3 + [41] * 3
    assert plan.hi[:3 * steps].tolist() == [61] * 3 + [61] * 3 + [73] * 3


# --------------------------------------------------------------------------- #
# the programs: the same engine with and without the run writer
# --------------------------------------------------------------------------- #

def _engine():
    cfg = LlamaConfig(vocab_size=128, hidden_size=128, intermediate_size=128,
                      num_hidden_layers=1, num_attention_heads=1,
                      num_key_value_heads=1, max_position_embeddings=256,
                      dtype=jnp.float32)
    assert cfg.head_dim == 128
    model = LlamaForCausalLM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 {"input_ids": jnp.zeros((1, 8), jnp.int32)}
                                 )["params"]
    return InferenceEngineV2(
        model=model, model_parameters=params,
        config={"state_manager": {"max_tracked_sequences": 4,
                                  "max_ragged_sequence_count": 4,
                                  "max_ragged_batch_size": 4 + 3 * 16,
                                  "prefill_chunk_size": 16,
                                  "max_context": 256},
                "kv_cache": {"block_size": 16}, "dtype": jnp.float32})


def _serve(eng):
    """Prompts that take the paged pass every way: a long one over several
    slots and passes, a continuation from an odd position beside decode
    rows, a short one; then the pool and every logit row."""
    rng = np.random.RandomState(3)
    out = [eng.put([1, 2], [rng.randint(0, 128, size=(n,)).astype(np.int32)
                            for n in (75, 9)])]
    out.append(eng.put([1, 2, 3], [rng.randint(0, 128, size=(n,)).astype(
        np.int32) for n in (1, 37, 21)]))
    out.append(eng.put([2, 3], [rng.randint(0, 128, size=(n,)).astype(
        np.int32) for n in (1, 1)]))
    return [np.asarray(x) for o in out for x in o], np.asarray(eng.kv.kv)


def test_the_paged_pass_serves_what_the_row_scatter_served(monkeypatch):
    """The engine's paged passes with the run writer and, the writer turned
    away, with the row scatter: the same logits, the same pool — and the
    always-on counters say which rows went how."""
    from deepspeed_tpu.monitor.trace import tracer
    rows = lambda: [tracer.totals.get(f"serve/kv_write/{k}", 0.0)
                    for k in ("run_rows", "single_rows")]
    eng = _engine()
    assert rm.kv_write_run_group(eng.kv.kv, 16) == 16
    assert rm.kv_write_run_group(eng.kv.kv, 16, tp=2) is None
    before = rows()
    logits, pool = _serve(eng)
    # the PAGED passes' rows (48 rows a pass: 27 of the first prompt's 75
    # beside the second's 9, then 37 beside a decode row; the other passes
    # start from zero and write whole pages), and 1 + 1 + 1 decode rows
    assert [b - a for a, b in zip(before, rows())] == [73.0, 3.0]
    monkeypatch.setattr(rm, "kv_run_group", lambda *a: None)
    before = rows()
    want_logits, want_pool = _serve(_engine())
    assert [b - a for a, b in zip(before, rows())] == [0.0, 76.0]
    for got, want in zip(logits, want_logits):
        np.testing.assert_array_equal(got, want)
    live = np.arange(pool.shape[1]) != pool.shape[1] - 1    # (the scratch)
    np.testing.assert_array_equal(pool[:, live], want_pool[:, live])
