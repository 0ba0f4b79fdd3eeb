"""``ops/pallas/grouped_matmul.py`` against ``jax.lax.ragged_dot`` (the
kernel interpreted on the CPU, float32 at tiny widths), its plan of visits
against a plain count, and ``_moe_ffn`` on both sides of the shape rule that
chooses between the two kernels. The chip's compiler sees the kernel in
``test_chip_compile.py``; times are ``scripts/moe_grouped_table.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import ragged_model as rm
from deepspeed_tpu.monitor.trace import tracer
from deepspeed_tpu.ops.pallas.grouped_matmul import (grouped_matmul,
                                                     plan_visits, rhs_tiles,
                                                     row_tile)

# sizes of a layer's groups, rows the lhs has (a multiple of the row tile 8)
CASES = {
    "groups_of_0_1_7_8_9_rows": ([0, 1, 7, 8, 9, 0, 3], 32),
    "all_empty_but_one": ([0, 0, 5, 0], 8),
    "all_empty": ([0, 0, 0, 0], 8),
    "every_group_touched": ([4, 4, 4, 4], 16),
    "every_group_one_row": ([1] * 8, 8),
    "a_group_over_three_tiles": ([3, 20, 1], 24),
    "rows_padded_to_8": ([2, 0, 3], 8),
    "held_rows_of_no_group": ([2, 0, 1, 3], 40),      # 34 rows went elsewhere
    "held_nothing_landed_here": ([0, 0], 16),
    "last_group_ends_on_a_tile": ([8, 8], 24),
}


def _operands(sizes, m, L, K, N, dtype=jnp.float32, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    lhs = jax.random.normal(k1, (m, K), dtype)
    stack = jax.random.normal(k2, (L, len(sizes), K, N), dtype)
    return lhs, stack, jnp.asarray(sizes, jnp.int32)


def _run(lhs, stack, sizes, l, tm=8, tiles=None):
    K, N = stack.shape[-2:]
    return jax.jit(lambda a, s, g, li: grouped_matmul(
        a, s.reshape(-1, K, N), plan_visits(g, a.shape[0], tm), li,
        tiles=tiles))(lhs, stack, sizes, jnp.int32(l))


@pytest.mark.parametrize("case", sorted(CASES))
def test_grouped_matmul_is_ragged_dot_on_the_rows_of_groups(case):
    """Layer 1 of a three-layer stack whose other layers are NaN (a read of
    them shows), ``l`` traced as a layer scan traces it: the rows of groups
    are ``ragged_dot``'s on the layer's slice, bitwise in float32 at one K
    block; rows of no group in a visited tile are 0."""
    sizes, m = CASES[case]
    lhs, stack, gs = _operands(sizes, m, L=3, K=32, N=64)
    poisoned = jnp.full_like(stack, jnp.nan).at[1].set(stack[1])
    got = np.asarray(_run(lhs, poisoned, gs, 1))
    want = np.asarray(jax.lax.ragged_dot(lhs, stack[1], gs))
    n = sum(sizes)
    np.testing.assert_array_equal(got[:n], want[:n])
    visited = -(-n // 8) * 8         # the tiles some group has rows in
    assert not got[n:visited].any()


@pytest.mark.parametrize("tiles", [(128, 128), (256, 128), (128, 256)],
                         ids=lambda t: f"tk{t[0]}_tn{t[1]}")
@pytest.mark.parametrize("dtype,tm", [(jnp.float32, 8), (jnp.bfloat16, 16)],
                         ids=["f32", "bf16"])
def test_grouped_matmul_cut_along_k_and_n(dtype, tm, tiles):
    """The rhs block smaller than the matrix: K blocks accumulate in float32,
    N blocks walk the visits again; a group longer than a row tile meets its
    matrix in more than one visit."""
    sizes, m = [20, 0, 1, 30], 64
    lhs, stack, gs = _operands(sizes, m, L=2, K=256, N=256, dtype=dtype)
    got = np.asarray(_run(lhs, stack, gs, 1, tm=tm, tiles=tiles), np.float32)
    want = np.asarray(jax.lax.ragged_dot(
        lhs, stack[1], gs, preferred_element_type=jnp.float32), np.float32)
    n = sum(sizes)
    tol = 1e-4 if dtype == jnp.float32 else 0.15    # of values up to 60
    np.testing.assert_allclose(got[:n], want[:n], atol=tol, rtol=1e-2)


@pytest.mark.parametrize("tm", [8, 16, 128])
@pytest.mark.parametrize("case", sorted(CASES))
def test_plan_visits_lists_each_touched_group_and_tile_once(case, tm):
    sizes, _ = CASES[case]
    m = -(-max(sum(sizes), 1) // tm) * tm + tm          # a tile of no group
    plan = plan_visits(jnp.asarray(sizes, jnp.int32), m, tm)
    want, start = [], 0
    for g, n in enumerate(sizes):
        want += [(g, t) for t in range(start // tm, -(-(start + n) // tm))
                 if n]
        start += n
    count = int(plan.count[0])
    assert count == max(len(want), 1)
    got = list(zip(np.asarray(plan.group)[:count].tolist(),
                   np.asarray(plan.tile)[:count].tolist()))
    assert got[:len(want)] == want
    assert np.asarray(plan.offsets).tolist() == [0] + np.cumsum(sizes).tolist()
    assert plan.group.shape == (m // tm + len(sizes) - 1,)
    if not want:            # the one visit of an empty plan stores nothing
        assert got == [(0, 0)]


def test_rhs_tiles_keep_a_small_matrix_whole_and_cut_a_large_one():
    assert rhs_tiles(2048, 1024, 2) == (2048, 1024)       # Trinity, 4 MiB
    assert rhs_tiles(768, 2048, 2) == (768, 2048)         # JoyAI, 3 MiB
    for k, n in ((4096, 14336), (14336, 4096)):           # Mixtral, 112 MiB
        tk, tn = rhs_tiles(k, n, 2)
        assert k % tk == 0 and n % tn == 0 and tn % 128 == 0
        assert tk * tn * 2 <= 8 << 20
    assert row_tile(8) == row_tile(256) == 64 and row_tile(8192) == 128


# --------------------------------------------------------------------------- #
# _moe_ffn: the rule, and both of its sides
# --------------------------------------------------------------------------- #

BF16 = jnp.bfloat16


def _sds(*shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("stack,dtype,want", [
    (_sds(4, 128, 2048, 1024), BF16, "pallas"),           # Trinity-Mini
    (_sds(4, 128, 1024, 2048), BF16, "pallas"),
    (_sds(39, 16, 2048, 768), BF16, "pallas"),            # JoyAI's held 16
    (_sds(16, 768, 2048), BF16, "pallas"),                # one layer's slice
    (_sds(3, 8, 4096, 14336), BF16, "xla"),               # Mixtral, 112 MiB
    ({"w8": _sds(8, 2048, 1024, dtype=jnp.int8),
      "scale": _sds(8, 1, 1024, dtype=jnp.float32)}, BF16, "xla"),
    (_sds(4, 128, 2048, 1024, dtype=jnp.float32), jnp.float32, "xla"),
    (_sds(4, 128, 2048, 1024), jnp.float32, "xla"),
    (_sds(3, 8, 64, 32), BF16, "xla"),                    # not whole lane tiles
], ids=["trinity_up", "trinity_down", "joyai_up", "joyai_sliced", "mixtral",
        "int8", "f32", "f32_rows", "narrow"])
def test_kernel_choice_reads_static_shapes_only(stack, dtype, want):
    assert rm.moe_grouped_kernel(stack, dtype) == want


def _moe_weights(L, E, hid, F, seed=0, shared=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    w = {"router": jax.random.normal(ks[0], (hid, E), jnp.float32),
         "w_gate": jax.random.normal(ks[1], (L, E, hid, F), BF16) * .1,
         "w_up": jax.random.normal(ks[2], (L, E, hid, F), BF16) * .1,
         "w_down": jax.random.normal(ks[3], (L, E, F, hid), BF16) * .1}
    if shared:
        w["expert_bias"] = jax.random.normal(ks[4], (E,), jnp.float32) * .1
        w["shared"] = {
            "w_gate": jax.random.normal(ks[5], (hid, F), BF16) * .1,
            "w_up": jax.random.normal(ks[6], (hid, F), BF16) * .1,
            "w_down": jax.random.normal(ks[7], (F, hid), BF16) * .1}
    return w


@pytest.mark.parametrize("T", [1, 5, 32])
@pytest.mark.parametrize("routing", ["softmax", "sigmoid_shared", "held"])
def test_moe_ffn_gives_the_same_on_both_sides_of_the_rule(routing, T,
                                                          monkeypatch):
    """One input through the Pallas side (bfloat16 stacks of whole lane
    tiles) and, with the rule's bound on the matrix set to nothing, through
    XLA's: the same output to bfloat16's rounding, at layer 2 of a stack
    whose other layers are NaN on the Pallas side, with the counter saying which side ran."""
    L, E, hid, F, top_k = 3, 8, 128, 256, 2
    w = _moe_weights(L, E, hid, F, shared=routing != "softmax")
    spec = None
    if routing != "softmax":
        spec = dict(score_func="sigmoid", route_norm=True, route_scale=2.0)
    if routing == "held":       # the stacks hold experts 2..5 of the router's 8
        spec["held"] = (2, 4)
        for k in ("w_gate", "w_up", "w_down"):
            w[k] = w[k][:, 2:6]
    x = jax.random.normal(jax.random.PRNGKey(3), (T, hid), BF16)
    run = lambda w: jax.jit(lambda xx, li: rm._moe_ffn(
        xx, w, top_k, BF16, li, routing=spec))(x, jnp.int32(2))
    # the CPU's ragged_dot multiplies by every group and masks afterwards,
    # so only the Pallas side is shown the other layers as NaN
    poisoned = dict(w, **{k: jnp.full_like(w[k], jnp.nan).at[2].set(w[k][2])
                          for k in ("w_gate", "w_up", "w_down")})

    before = dict(tracer.totals)
    got = np.asarray(run(poisoned), np.float32)
    monkeypatch.setattr(rm, "GROUPED_PALLAS_MATRIX_BYTES", 0)
    want = np.asarray(run(w), np.float32)
    gained = {k: v - before.get(k, 0) for k, v in tracer.totals.items()
              if k.startswith("serve/moe/grouped_kernel/")}
    assert gained == {"serve/moe/grouped_kernel/pallas": 1,
                      "serve/moe/grouped_kernel/xla": 1}
    assert np.isfinite(got).all() and np.isfinite(want).all()
    scale = np.abs(want).max()
    assert scale > 0.1
    np.testing.assert_allclose(got, want, atol=2e-2 * scale, rtol=0)


# --------------------------------------------------------------------------- #
# an engine whose MoE layers take the Pallas kernel
# --------------------------------------------------------------------------- #

def _afmoe_engine_logits():
    """A bfloat16 afmoe model of whole lane tiles (hidden 128, experts of 128)
    through the engine's packed prefill, a paged chunk, single-token passes
    and the fused decode step."""
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models.afmoe import AfmoeConfig, AfmoeForCausalLM
    cfg = AfmoeConfig.tiny(dtype=BF16, hidden_size=128, head_dim=32,
                           intermediate_size=256, moe_intermediate_size=128)
    model = AfmoeForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = InferenceEngineV2(model=model, model_parameters=params, config={
        "dtype": "bfloat16",
        "state_manager": {"max_context": 128, "max_tracked_sequences": 4,
                          "max_ragged_sequence_count": 4,
                          "max_ragged_batch_size": 4 + 2 * 16,
                          "prefill_chunk_size": 16},
        "kv_cache": {"block_size": 8, "num_blocks": 64}})
    ids = np.random.default_rng(0).integers(0, 256, 40).astype(np.int32)
    out = [eng.put([1], [ids[:24]])[0], eng.put([1], [ids[24:36]])[0]]
    out += [eng.put([1], [ids[i:i + 1]])[0] for i in range(36, 40)]
    eng.flush([1])
    eng.put([2], [ids[:36]])
    return np.stack([np.asarray(o, np.float32) for o in out]), [
        int(t) for t in eng.decode_pipeline([2]).run(2)[0]]


def test_engine_serves_the_same_logits_on_either_grouped_kernel(monkeypatch):
    """Every program of an engine (packed and paged prefill, the ragged pass,
    the fused decode step) with its MoE layers on the Pallas kernel, against
    the same engine held to XLA's: the logits of six passes agree to
    bfloat16's rounding through four layers."""
    before = dict(tracer.totals)
    got, got_tokens = _afmoe_engine_logits()
    took = {k: v - before.get(k, 0) for k, v in tracer.totals.items()
            if k.startswith("serve/moe/grouped_kernel/")}
    assert took.get("serve/moe/grouped_kernel/pallas", 0) >= 4 and \
        not took.get("serve/moe/grouped_kernel/xla")
    monkeypatch.setattr(rm, "GROUPED_PALLAS_MATRIX_BYTES", 0)
    want, want_tokens = _afmoe_engine_logits()
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= 2e-2 * np.abs(want).max()
    assert len(got_tokens) == len(want_tokens) == 2
