"""``glm_dsa_ref.py`` against the program's own glm_dsa model at tiny widths,
same weights, whole and as one chip's share of the experts; that the
reference notices each way to serve the selection wrongly, by the limit that
is said to catch it (the logits' or the selection's own); the configuration
file's account of the two pools against what the engine allocates; that the
cell's longest request fits the engine; that sixteen chips' shares add up to
the uncut layer; and the selection kernels' operations and bytes
(``chipbench/reduce/dsa_work.py``)."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CONFIG = json.load(open(os.path.join(
    ROOT, "chipbench", "configs", "glm5-serve-ep16.json")))
CELL = "glm5-serve-ep16.longctx-closed-16"
BASE = dict(family="glm_dsa", vocab_size=256, hidden_size=64,
            intermediate_size=128, moe_intermediate_size=32,
            num_hidden_layers=3, first_k_dense_replace=1,
            num_attention_heads=4, num_key_value_heads=4, q_lora_rank=48,
            kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
            v_head_dim=48, max_position_embeddings=512,
            rope_parameters={"rope_theta": 1e4, "rope_type": "default"},
            rms_norm_eps=1e-5, n_routed_experts=16, n_shared_experts=1,
            num_experts_per_tok=4, norm_topk_prob=True,
            routed_scaling_factor=2.5, scoring_func="sigmoid",
            topk_method="noaux_tc", n_group=1, topk_group=1,
            index_n_heads=4, index_head_dim=32, index_topk=16)
#: experts 4-7 of the 16: a configuration file's spelling of one chip's share
HELD = dict(BASE, n_routed_experts=4, published={"n_routed_experts": 16},
            deployment={"held_first": 4})
#: what the engine is held to on the chip in bfloat16
CHIP_TOL = CONFIG["check"]["tol_logits"]
TOL_INDEX = CONFIG["check"]["tol_index"]


def family():
    from chipbench.harness import Registry
    return Registry().module("families", "glm_dsa")


def setup(cfg, seed=2**31 + 5):
    import jax.numpy as jnp
    from chipbench import models
    fam = family()
    model = fam.build_model(cfg, jnp.float32)
    params = models.init_params(model, seed, jnp.float32)
    return model, params, fam.reference_weights(params, cfg), \
        fam.reference_hp(cfg)


@pytest.mark.parametrize("case", ["all_experts", "held_share"])
def test_reference_agrees_with_the_zoo(case):
    import jax
    import jax.numpy as jnp
    from chipbench.reference import glm_dsa_ref
    model, params, weights, hp = setup(BASE if case == "all_experts" else HELD)
    assert (hp["held"] is None) == (case == "all_experts")
    assert hp["index_topk"] == 16 and hp["index_rope_dim"] == 16
    ids = np.random.default_rng(0).integers(0, 256, (2, 40)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = model.apply({"params": params}, ids, method="forward_logits")
    for b in range(2):
        got = glm_dsa_ref.forward_logits(weights, jnp.asarray(ids[b]), hp)
        scale = float(jnp.max(jnp.abs(want[b])))
        assert float(jnp.max(jnp.abs(got - want[b]))) < 1e-4 * scale
    picked, m = glm_dsa_ref.forward_logits(
        weights, jnp.asarray(ids[0]), hp, rows=jnp.asarray([3, 39]),
        with_margin=True)
    assert picked.shape == (2, 256) and m.shape == (2,)


def test_weights_made_a_layer_at_a_time_are_the_models_tree():
    import jax
    import jax.numpy as jnp
    from chipbench import models
    fam = family()
    model = fam.build_model(HELD, jnp.float32)
    whole = models.init_params(model, 7, jnp.bfloat16)
    made = fam.init_params(model, 7, jnp.bfloat16)
    spec = lambda t: jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), t)
    assert spec(made) == spec(whole)
    ix = made["layers_1"]["self_attn"]["indexer"]
    assert sorted(ix) == ["k_norm", "weights_proj", "wk", "wq_b"]
    assert ix["wq_b"]["kernel"].shape == (48, 4 * 32)


#: fault -> what of it the selection's own check can see. At these widths
#: (16 tokens kept of 64) every fault also moves the logits by more than
#: twice the engine's tolerance on the chip; at the published widths one key
#: of 2,048 is a two-thousandth of a row's attention, and the threshold off
#: by one is the selection check's alone to catch. ``chunk_shared`` changes
#: what the queries of a chunk attend to, not what a query's indexer
#: selects: the logits' limits catch it
FAULTS = {
    "dense": True,                 # the selection dropped
    "abs_topk": True,              # top-k by |I|
    "no_relu": True,
    "unsigned_weights": True,
    "rope_tail": True,             # the rotated half swapped
    "keys_late": True,             # index keys written one position late
    "topk_minus_one": True,        # the threshold off by one
    "chunk_shared": False,         # a chunk's queries share a selection
}


@pytest.fixture(scope="module")
def sound():
    import jax.numpy as jnp
    from chipbench.reference import glm_dsa_ref
    _, _, weights, hp = setup(HELD, seed=3)
    hp = dict(hp, chunk=16)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 256, 64), jnp.int32)
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
    cq = jnp.asarray(rng.standard_normal((32, 48)), jnp.float32)
    rows = jnp.arange(32, 64)
    ix = weights["layers"][1]["index"]
    items = lambda d: tuple(sorted(d.items()))
    read = lambda hp_: glm_dsa_ref.index_readings(ix, h, cq, rows, items(hp_))
    return {"weights": weights, "hp": hp, "ids": ids, "read": read,
            "logits": np.asarray(glm_dsa_ref.forward_logits(weights, ids, hp)),
            "index": read(hp)}


def _selection_differs(sound, hp):
    """Positions one selection keeps and the other does not, further than
    the chip's ``tol_index`` (in units of the row's score spread) from the
    sound reference's threshold: what ``families/glm_dsa.py::
    selection_readings`` counts."""
    scores, want, thr = (np.asarray(x) for x in sound["index"])
    _, got, _ = (np.asarray(x) for x in sound["read"](hp))
    seen = np.isfinite(scores)
    spread = np.array([scores[i][seen[i]].std() for i in range(len(scores))])
    away = np.where(seen, np.abs(np.where(seen, scores, 0) - thr[:, None])
                    / spread[:, None], 0.0)
    return int(((got != want) & (away > TOL_INDEX)).sum()
               + (got.sum(-1) != want.sum(-1)).sum())


@pytest.mark.parametrize("fault", list(FAULTS))
def test_reference_catches(sound, fault):
    from chipbench.reference import glm_dsa_ref
    hp = dict(sound["hp"], fault=fault)
    wrong = np.asarray(glm_dsa_ref.forward_logits(sound["weights"],
                                                  sound["ids"], hp))
    right = sound["logits"]
    late = slice(20, None)          # the selection starts at position 16
    err = np.max(np.abs(wrong[late] - right[late])) / np.max(np.abs(right))
    assert np.isfinite(wrong).all() and err > 2 * CHIP_TOL, err
    assert (_selection_differs(sound, hp) > 0) == FAULTS[fault]
    # positions below the selection's size see everything either way
    early = np.max(np.abs(wrong[:12] - right[:12])) / np.max(np.abs(right))
    if fault in ("dense", "abs_topk", "topk_minus_one", "chunk_shared"):
        assert early < 1e-5


def test_selection_control_is_over_the_limit_and_the_reference_is_not(sound):
    """The indexer in float8 places keys on the other side of a threshold
    further than ``tol_index`` from it."""
    import jax.numpy as jnp
    assert _selection_differs(sound, sound["hp"]) == 0
    assert _selection_differs(
        sound, dict(sound["hp"], index_dtype=jnp.float8_e4m3fn)) > 0


def test_selection_keeps_exactly_topk_with_the_lower_position_first():
    import jax.numpy as jnp
    from chipbench.reference import glm_dsa_ref
    hp = {"index_topk": 3}
    scores = jnp.asarray([[1.0, 2.0, 2.0, 2.0, 2.0, -jnp.inf],
                          [5.0, 1.0, -jnp.inf, -jnp.inf, -jnp.inf, -jnp.inf]])
    keep = np.asarray(glm_dsa_ref.selection(scores, hp))
    assert keep.tolist() == [[False, True, True, True, False, False],
                             [True, True, False, False, False, False]]


def test_lower_precision_moves_the_reference(sound):
    import jax.numpy as jnp
    from chipbench.reference import glm_dsa_ref
    right = sound["logits"]
    scale = np.max(np.abs(right))

    def err(**kw):
        got = np.asarray(glm_dsa_ref.forward_logits(
            sound["weights"], sound["ids"], sound["hp"], **kw))
        return np.median(np.max(np.abs(got - right), axis=-1)) / scale

    assert err(act_dtype=jnp.float8_e4m3fn) > CHIP_TOL
    assert err(act_dtype=jnp.float8_e4m3fn, rounding=False) == 0


def test_memory_account_is_the_pools_the_engine_allocates():
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.ragged.kv_cache import KVCacheConfig
    cfg = CONFIG
    layout = family().page_layout(cfg)
    assert layout == {"layers": 5, "row_values": 576, "latent_row_dim": 640,
                      "index_dim": 128, "latent_dim": 768}
    account = cfg["memory_account_numbers"]
    bs = cfg["engine"]["kv_cache"]["block_size"]
    # the driver funds both pools through latent_dim; the engine allocates
    # a latent pool and an index pool of the same page count
    funded = KVCacheConfig.from_memory_budget(
        layout["layers"], 0, 0, account["page_budget_bytes"], block_size=bs,
        dtype=jnp.bfloat16, latent_dim=layout["latent_dim"])
    engine = KVCacheConfig(layout["layers"], 64, 256, bs, funded.num_blocks,
                           jnp.bfloat16, latent_dim=640, index_dim=128)
    assert funded.bytes_per_block() == engine.bytes_per_block() \
        == account["bytes_a_page"] == 5 * 128 * 1536
    assert account["bytes_a_token_a_layer"] == 1536 \
        == account["latent_bytes_a_token_a_layer"] \
        + account["index_bytes_a_token_a_layer"]
    assert funded.num_blocks == account["pages"] == 6436
    assert account["tokens"] == account["pages"] * bs
    assert account["page_budget_bytes"] == int(
        account["hbm_limit_bytes"] * cfg["hbm_fill"]) \
        - account["weight_bytes"] - cfg["hbm_headroom_bytes"]
    assert abs(account["weight_bytes"] / 2**30 - 7.28) < 0.05


def test_weight_bytes_are_the_models():
    """3.910B parameters at the cut the file states: one dense layer, four
    MoE layers of 16 held experts, an eighth of the vocabulary."""
    import math
    import jax
    import jax.numpy as jnp
    model = family().build_model(CONFIG, jnp.bfloat16)
    shapes = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    count = lambda t: sum(math.prod(a.shape)
                          for a in jax.tree_util.tree_leaves(t))
    assert 2 * count(shapes) == CONFIG["memory_account_numbers"][
        "weight_bytes"]
    assert count(shapes["layers_0"]) == 400_898_816
    assert count(shapes["layers_1"]) == 817_708_032
    assert count(shapes["layers_1"]["self_attn"]["indexer"]) == 9_371_904


def test_the_cells_longest_request_fits_the_engine():
    traffic = json.load(open(os.path.join(
        ROOT, "chipbench", "traffic", "longctx-closed-16.json")))
    sm = CONFIG["engine"]["state_manager"]
    longest = traffic["prompt_tokens"]["max"] \
        + traffic["output_tokens"]["max"] + 8 + 1     # one decode slice more
    assert longest <= sm["max_context"] == 34816
    assert sm["max_context"] % CONFIG["engine"]["kv_cache"]["block_size"] == 0
    assert traffic["clients"] == sm["max_ragged_sequence_count"] == 16
    # a packed pass holds no more tokens than the selection keeps
    slots = (sm["max_ragged_batch_size"] - sm["max_ragged_sequence_count"])
    assert slots == 8 * sm["prefill_chunk_size"] <= CONFIG["index_topk"]
    # contexts are 4-16 times the selection
    assert traffic["prompt_tokens"]["min"] == 4 * CONFIG["index_topk"]
    assert traffic["prompt_tokens"]["max"] == 16 * CONFIG["index_topk"]
    # rows bind, not pages
    tokens = CONFIG["memory_account_numbers"]["tokens"]
    assert 16 * sm["max_context"] < tokens


def test_the_family_balances_each_router_on_the_models_own_states(
        seed=2**31 + 9):
    """``init_params`` leaves every weight as drawn but each MoE layer's
    ``e_score_correction_bias``, and on fresh sequences the experts' loads
    lie nearer the even share than under the drawn bias (the widest gap
    between two experts' loads, in even shares: narrower in every layer, by
    a fifth and more in their sum)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.glm_dsa import GlmDsaBlock
    from deepspeed_tpu.models.joyai import route
    fam = family()
    model = fam.build_model(dict(HELD, num_hidden_layers=4), jnp.float32)
    cfg = model.config
    kept = fam.balance
    try:
        fam.balance = lambda *a, **k: None
        drawn = fam.init_params(model, seed, jnp.float32)
    finally:
        fam.balance = kept
    balanced = fam.init_params(model, seed, jnp.float32)
    changed = {jax.tree_util.keystr(path) for path, same in
               jax.tree_util.tree_leaves_with_path(jax.tree_util.tree_map(
                   lambda a, b: bool(jnp.array_equal(a, b)), drawn, balanced))
               if not same}
    assert changed == {f"['layers_{i}']['mlp']['e_score_correction_bias']"
                       for i in (1, 2, 3)}

    def widest(params):
        ids = jax.random.randint(jax.random.PRNGKey(123), (8, 256), 0,
                                 cfg.vocab_size)
        pos = jnp.broadcast_to(jnp.arange(256)[None], ids.shape)
        x = jnp.take(params["embed_tokens"]["embedding"], ids, axis=0)
        spans = []
        for i in range(cfg.num_hidden_layers):
            p = params[f"layers_{i}"]
            x, seen = GlmDsaBlock(cfg, i).apply(
                {"params": p}, x, pos, mutable=["intermediates"],
                capture_intermediates=lambda m, _:
                m.name == "post_attention_layernorm")
            if cfg.is_moe_layer(i):
                h = seen["intermediates"]["post_attention_layernorm"][
                    "__call__"][0].reshape(-1, cfg.hidden_size)
                _, chosen = route(
                    h.astype(jnp.float32) @ p["mlp"]["gate"]["kernel"],
                    p["mlp"]["e_score_correction_bias"], cfg)
                load = np.bincount(np.asarray(chosen).ravel(),
                                   minlength=cfg.n_routed_experts)
                spans.append((load.max() - load.min()) / load.mean())
        return spans

    was, now = widest(drawn), widest(balanced)
    assert len(was) == 3 and all(n < w for n, w in zip(now, was)) \
        and sum(now) < 0.8 * sum(was), (was, now)


def test_every_seed_is_dealt_the_cells_one_order():
    """The lengths, their order and their pairing are the cell's
    (``pool_order``: what ``traffic/balanced.py`` deals at that number);
    the token ids are the run's seed's."""
    from chipbench.harness import Registry
    from chipbench.traffic import balanced
    reg = Registry()
    driver = reg.module("drivers", "serve_closed_selected")
    mix = dict(reg.traffic("longctx-closed-16"), pool_requests=32)
    order = reg.cell(CELL)["pool_order"]
    one, two = (driver.dealt(mix, order, seed, 19360)
                for seed in (2**31 + 7, 11))
    want = balanced.closed_pool(mix, order, 19360)
    shape = lambda pool: [(len(r.prompt), r.max_new_tokens) for r in pool]
    assert shape(one) == shape(two) == shape(want)
    assert shape(one) != shape(balanced.closed_pool(mix, order + 1, 19360))
    assert not np.array_equal(one[0].prompt, two[0].prompt)
    assert np.array_equal(one[0].prompt,
                          driver.dealt(mix, order, 2**31 + 7, 19360)[0].prompt)
    assert all(r.prompt.dtype == np.int32 and 0 <= r.prompt.min()
               and r.prompt.max() < 19360 for r in one)


def test_the_mean_over_windows_by_hand():
    """Windows of 10 s opened over 4 s: a token 2 s after the ramp lies in
    the windows opened in [0, 2] (half of them), one at 7 s in all, one at
    12 s in those opened in [2, 4], one at 14 s in none; a steady stream
    reads its own rate."""
    from types import SimpleNamespace
    from chipbench.harness import Registry
    driver = Registry().module("drivers", "serve_closed_selected")
    ctx = SimpleNamespace(log=lambda msg: None)
    handle = lambda times: SimpleNamespace(
        arrival_t=100.0, ttft_ms=1e3 * (times[0] - 100.0),
        tbt_ms=list(1e3 * np.diff(times)))
    got = driver.windows_mean(
        ctx, [handle([102.0, 107.0]), handle([112.0, 114.0])], 100.0, 10.0,
        4.0)
    assert got == pytest.approx((0.5 + 1.0 + 0.5 + 0.0) / 10.0)
    steady = [handle(list(95.0 + 0.01 * np.arange(3000)))]
    assert driver.windows_mean(ctx, steady, 100.0, 10.0, 4.0) == \
        pytest.approx(100.0, rel=1e-3)
    cell = Registry().cell(CELL)
    assert 0 < cell["window_opens_over_s"] <= cell["trace_tail_s"]


def test_published_keys_are_the_catalogs():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in open(catalog)
               if json.loads(line)["name"] == "GLM-5")
    assert CONFIG["source"] == row["source_url"]
    reduced = set(CONFIG["reduced"])
    assert reduced == {"num_hidden_layers", "first_k_dense_replace",
                       "n_routed_experts", "vocab_size"}
    for key, value in row["config"].items():
        if key in reduced:
            assert CONFIG["published"][key] == value
        else:
            assert CONFIG[key] == value, key
    assert (CONFIG["num_hidden_layers"], CONFIG["first_k_dense_replace"],
            CONFIG["n_routed_experts"], CONFIG["vocab_size"]) == (
                5, 1, 16, 19360)
    assert CONFIG["vocab_size"] * 8 == row["config"]["vocab_size"]


def test_the_cell_is_in_the_benchmark_by_membership():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["chips"] == 1
    assert cells[CELL]["config"] == "glm5-serve-ep16"
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [])}
    assert {"dsa_index_share.longctx", "dsa_select_share.longctx",
            "dsa_attend_share.longctx",
            "dsa_index_decode_roofline_share.longctx",
            "dsa_attend_decode_roofline_share.longctx", "mla_share.assist",
            "mla_absorb_share.assist", "kv_flush_share.serve"} <= mine
    assert "mla_decode_roofline_share.assist" not in mine
    assert all("workloads" in m for m in bench["per_layer"]
               if m["name"].startswith("dsa_"))
    # the two chunk kernels' roofline readers stay tools: a capture can hold
    # paged passes and none of their host spans
    assert not [n for n in mine if "chunk_roofline" in n]


def test_sixteen_shares_add_up_to_the_uncut_layer():
    """At a small size: the routed parts of the 16 chips' shares of 16 x 2
    experts, plus the shared expert once, are the uncut reference's MoE
    layer."""
    import jax
    import jax.numpy as jnp
    from chipbench.reference import glm_dsa_ref
    from deepspeed_tpu.inference.v2 import ragged_model as rm
    from deepspeed_tpu.models.glm_dsa import GlmDsaConfig
    wide = dict(BASE, n_routed_experts=32, num_experts_per_tok=8)
    model, params, weights, hp = setup(wide, seed=11)
    layer = {k: v for k, v in weights["layers"][2].items()
             if k in ("router", "expert_bias", "w_gate", "w_up", "w_down",
                      "shared")}
    spec, stacks = rm.adapt_glm_dsa(params, model.config)
    assert isinstance(model.config, GlmDsaConfig) and "held" not in spec.moe
    w = jax.tree_util.tree_map(lambda a: a[1], stacks["layers"][1]["moe"])
    x = jnp.asarray(np.random.default_rng(5).standard_normal((24, 64)),
                    jnp.float32)
    err = lambda a, b: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
    with jax.default_matmul_precision("highest"):
        want, _ = glm_dsa_ref.sparse_mixture(x, layer, hp)
        shared = rm._swiglu(x, w["shared"])
        routed = {k: v for k, v in w.items() if k != "shared"}
        parts = []
        for first in range(0, 32, 2):
            mine = dict(routed, **{k: routed[k][first:first + 2]
                                   for k in ("w_gate", "w_up", "w_down")})
            parts.append(rm._moe_ffn(x, mine, 8, jnp.float32,
                                     routing=dict(spec.moe,
                                                  held=(first, 2))))
    assert len(parts) == 16 and err(sum(parts) + shared, want) <= 1e-5
    assert all(err(p + shared, want) > 1e-2 for p in parts)


def test_kernel_work_at_the_published_widths():
    """8,192 operations and 256 B a cached token for the index; 139,264
    operations and 1,152 B a CHOSEN token for attention."""
    from chipbench.reduce import dsa_work, mla_work
    w = dsa_work.widths(CONFIG)
    assert dsa_work.index_pair_flops(w) == 32 * 128 * 2 == 8192
    assert dsa_work.attend_pair_flops(w) == 64 * (576 + 512) * 2 == 139264
    peaks = json.load(open(os.path.join(ROOT, "chipbench", "peaks.json")))[
        "TPU v5 lite"]
    # 16 rows at 20k: the index reads 256 B a token and is bound by bytes
    flops, bytes_ = dsa_work.index_decode_call(w, 16, 16 * 20000)
    assert flops == 320000 * 8192
    assert bytes_ == 320000 * (256 + 4) + 16 * 32 * (256 + 4)
    assert mla_work.roofline(flops, bytes_, 1.0, peaks)["bound"] == "memory"
    # attention over min(ctx, 2048) chosen rows a row, whatever the context
    flops, bytes_ = dsa_work.attend_decode_call(w, 16 * 2048, 16)
    assert flops == 16 * 2048 * 139264
    assert bytes_ == 16 * 2048 * 1152 + 16 * 64 * (576 + 512) * 2
    # a chunk slot of 4 queries after 10 cached tokens: 11 + 12 + 13 + 14
    # pairs scored; attention counts min(seen, topk) a query
    flops, bytes_ = dsa_work.index_chunk_call(w, [4, 0], [10, 0])
    assert flops == 50 * 8192
    assert bytes_ == 14 * 256 + 50 * 4 + 4 * 32 * 260
    small = dict(w, topk=12)
    flops, _ = dsa_work.attend_chunk_call(small, [4], [10])
    assert flops == (11 + 12 + 12 + 12) * 139264
    flops, _ = dsa_work.attend_chunk_call(w, [256], [20000])
    assert flops == 256 * 2048 * 139264
    assert mla_work.roofline(*dsa_work.attend_chunk_call(w, [256], [20000]),
                             1.0, peaks)["bound"] == "compute"


def test_readers_find_the_kernels_by_scope_and_program():
    """``readers/dsa.py`` on a trace written by hand: a decode row's
    attention over its selection is the gather under ``index/gather`` AND
    the kernel under ``mla/decode``, inside the decode-step program, against
    the captured span's ``live`` and ``ctx``; without a capture, without the
    widths or without such an operation a reader gives nothing."""
    from types import SimpleNamespace
    from chipbench.harness import Registry
    from chipbench.reduce import dsa_work
    from tests.chipbench.test_named import hand_trace
    dsa = Registry().module("readers", "dsa")
    assert set(dsa.SCOPES) == {"index_decode", "attend_decode",
                               "index_chunk", "attend_chunk"}
    assert dsa.index_decode_roofline_share({"config": CONFIG}) is None
    peaks = json.load(open(os.path.join(ROOT, "chipbench", "peaks.json")))[
        "TPU v5 lite"]
    step = "jit(serve_decode_step)/while/body/closed_call/attn/mla/"
    op_names = {"jit_serve_decode_step(1)": {
        "closed_call.21": step + "decode/dsa_attend_decode/pallas_call",
        "fusion.3": step + "index/gather/gather"}}
    record = ("X", "serve/decode/step", 10, 20, 0,
              {"step": 1, "live": 2, "ctx": 50000})
    capture = SimpleNamespace(records=[record], start_ns=0, stop_ns=1000)
    view = {"trace": hand_trace(), "op_names": op_names, "capture": capture,
            "config": CONFIG, "peaks": peaks}
    got = dsa.reading(view, "attend_decode")
    # two whole executions (0-400, and 800-1000 at another bucket, whose
    # names the trace does not carry): the fusion 0-100 and the kernel 100-300
    assert got["executions"] == 2 and got["kernel_us"] == pytest.approx(0.15)
    flops, bytes_ = dsa_work.attend_decode_call(
        dsa_work.widths(CONFIG), 2 * 2048, 2)
    floor = 5 * max(flops / 197e12, bytes_ / 819e9)
    assert got["floor_us"] == pytest.approx(floor * 1e6)
    assert dsa.attend_decode_roofline_share(view) == pytest.approx(
        100 * floor / 150e-9)
    assert dsa.reading(view, "index_decode") is None      # no such call
    assert dsa.reading(view, "attend_chunk") is None      # no paged pass
    assert dsa.reading(dict(view, config={}), "attend_decode") is None
    assert dsa.reading(dict(view, capture=None), "attend_decode") is None
