"""``joyai_ref.py`` against the program's own joyai model at tiny widths,
same weights, whole and as one chip's share of the experts; that the
reference notices each thing that sets the family apart: drop or change one
and the logits move by far more than the tolerance the engine is held to;
what the routing margin counts; the configuration file's account of the
latent pool against the pool the engine allocates; and the kernel's
operations and bytes (``chipbench/reduce/mla_work.py``)."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

BASE = dict(family="joyai", vocab_size=256, hidden_size=64,
            intermediate_size=128, moe_intermediate_size=32,
            num_hidden_layers=4, first_k_dense_replace=1,
            num_attention_heads=4, num_key_value_heads=4, q_lora_rank=48,
            kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
            v_head_dim=32, max_position_embeddings=512, rope_theta=1e4,
            rms_norm_eps=1e-6, n_routed_experts=16, n_shared_experts=1,
            num_experts_per_tok=4, norm_topk_prob=True,
            routed_scaling_factor=2.5, scoring_func="sigmoid",
            topk_method="noaux_tc", n_group=1, topk_group=1)
#: experts 4-7 of the 16: a configuration file's spelling of one chip's share
HELD = dict(BASE, n_routed_experts=4, published={"n_routed_experts": 16},
            deployment={"held_first": 4})
CASES = {
    "all_experts": BASE,
    "held_share": HELD,
    "no_norm_no_shared": dict(BASE, norm_topk_prob=False, n_shared_experts=0),
}
#: what the engine is held to on the chip in bfloat16
#: (chipbench/configs: check.tol_logits of the joyai configuration)
CHIP_TOL = json.load(open(os.path.join(
    ROOT, "chipbench", "configs", "joyai-flash-serve-ep16.json")))[
        "check"]["tol_logits"]


def family():
    from chipbench.harness import Registry
    return Registry().module("families", "joyai")


def setup(cfg, seed=2**31 + 5):
    import jax.numpy as jnp
    from chipbench import models
    fam = family()
    model = fam.build_model(cfg, jnp.float32)
    params = models.init_params(model, seed, jnp.float32)
    return model, params, fam.reference_weights(params, cfg), \
        fam.reference_hp(cfg)


@pytest.mark.parametrize("case", list(CASES))
def test_reference_agrees_with_the_zoo(case):
    import jax
    import jax.numpy as jnp
    from chipbench.reference import joyai_ref

    cfg = CASES[case]
    model, params, weights, hp = setup(cfg)
    assert (hp["held"] is None) == (case != "held_share")
    ids = np.random.default_rng(0).integers(0, 256, (2, 24)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = model.apply({"params": params}, ids, method="forward_logits")
    for b in range(2):
        got, margin = joyai_ref.forward_logits(
            weights, jnp.asarray(ids[b]), hp, with_margin=True)
        scale = float(jnp.max(jnp.abs(want[b])))
        assert float(jnp.max(jnp.abs(got - want[b]))) < 1e-4 * scale
        assert float(margin.min()) >= 0
    rows = jnp.asarray([3, 23])
    picked, m = joyai_ref.forward_logits(weights, jnp.asarray(ids[0]), hp,
                                         rows=rows, with_margin=True)
    assert picked.shape == (2, 256) and m.shape == (2,)


def test_weights_made_a_layer_at_a_time_are_the_models_tree():
    """``family.init_params`` (one small program a kind of layer, for the
    chip's compiler) gives ``model.init``'s tree, leaf for leaf in shape and
    type, every layer from a key of its own."""
    import jax
    import jax.numpy as jnp
    from chipbench import models
    fam = family()
    model = fam.build_model(HELD, jnp.float32)
    whole = models.init_params(model, 7, jnp.bfloat16)
    made = fam.init_params(model, 7, jnp.bfloat16)
    spec = lambda t: jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), t)
    assert spec(made) == spec(whole)
    a, b = (made[f"layers_{i}"]["mlp"]["w_gate"].astype(jnp.float32)
            for i in (1, 2))
    assert float(jnp.std(a - b)) > float(jnp.std(a))     # not one draw twice
    again = fam.init_params(model, 7, jnp.bfloat16)
    assert bool(jnp.array_equal(again["layers_3"]["mlp"]["w_up"],
                                made["layers_3"]["mlp"]["w_up"]))


def _with(**kw):
    return lambda w, hp: (w, dict(hp, **kw))


def _without(key):
    def change(w, hp):
        layers = [{k: v for k, v in layer.items() if k != key}
                  for layer in w["layers"]]
        return dict(w, layers=layers), hp
    return change


CHANGES = {
    "k_rope_unrotated": _with(k_rope_unrotated=True),
    "kv_a_layernorm_dropped": _without("kv_a_norm"),
    "scale_of_the_nope_width_only": _with(softmax_scale=32 ** -0.5),
    "routed_scaling_factor_dropped": _with(route_scale=1.0),
    "bias_used_as_weight": _with(weigh_with_bias=True),
    "shared_expert_dropped": _without("shared"),
    "normalised_over_the_held_choices_only": _with(norm_over_held=True),
}


@pytest.mark.parametrize("change", list(CHANGES))
def test_reference_changes_when(change):
    """Each is a way to serve the family wrongly that stays finite and
    plausible; the reference (one chip's share of the experts, as the cell
    runs it) moves by more than the engine's tolerance, so an engine doing
    it fails its check."""
    import jax.numpy as jnp
    from chipbench.reference import joyai_ref
    _, _, weights, hp = setup(HELD, seed=3)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 256, 40), jnp.int32)
    right = np.asarray(joyai_ref.forward_logits(weights, ids, hp))
    w2, hp2 = CHANGES[change](weights, hp)
    wrong = np.asarray(joyai_ref.forward_logits(w2, ids, hp2))
    late = slice(8, None)           # a first token attends itself alone
    err = np.max(np.abs(wrong[late] - right[late])) / np.max(np.abs(right))
    assert np.isfinite(wrong).all() and err > 2 * CHIP_TOL, err


def test_lower_precision_moves_the_reference():
    """The router's scores in bfloat16 change selections; activations handed
    on in float8 miss the chip's tolerance, in bfloat16 they meet it; with
    its rounding switched off the float8 program IS the float32 reference."""
    import jax.numpy as jnp
    from chipbench.reference import joyai_ref
    _, _, weights, hp = setup(HELD, seed=3)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 256, 40), jnp.int32)
    right, margin = (np.asarray(x) for x in joyai_ref.forward_logits(
        weights, ids, hp, with_margin=True))
    scale = np.max(np.abs(right))
    clear = margin > 0.02

    def err(**kw):
        got = np.asarray(joyai_ref.forward_logits(weights, ids, hp, **kw))
        return np.max(np.abs(got - right)[clear]) / scale

    assert clear.sum() >= 4
    assert err(act_dtype=jnp.bfloat16) < CHIP_TOL
    assert err(act_dtype=jnp.float8_e4m3fn) > CHIP_TOL
    assert err(act_dtype=jnp.float8_e4m3fn, rounding=False) == 0
    bf16_router = np.asarray(joyai_ref.forward_logits(
        weights, ids, dict(hp, router_dtype=jnp.bfloat16)))
    assert np.max(np.abs(bf16_router - right)) / scale > 1e-4


def test_margin_is_the_nearest_held_experts_distance_from_changing_sides():
    """With every expert held: the gap between the last expert chosen and
    the first left out. With a share held: how far the nearest held expert
    is from crossing that boundary, whoever the two at it are — never under
    the gap, and the gap itself where one of the two is held."""
    import jax.numpy as jnp
    from chipbench.reference import joyai_ref
    _, _, weights, hp = setup(HELD, seed=3)
    layer = weights["layers"][1]
    x = jnp.asarray(np.random.default_rng(2).standard_normal((200, 64)),
                    jnp.float32)
    dense, margin, is_held = joyai_ref.route(x, layer, hp)
    every, gap, _ = joyai_ref.route(x, layer, dict(hp, held=None))
    assert np.array_equal(np.asarray(dense), np.asarray(every))
    assert list(np.flatnonzero(np.asarray(is_held))) == [4, 5, 6, 7]
    margin, gap = np.asarray(margin), np.asarray(gap)
    scores = np.asarray(jnp.asarray(x) @ layer["router"])
    biased = 1 / (1 + np.exp(-scores)) + np.asarray(layer["expert_bias"])
    order = np.argsort(-biased, axis=-1)
    top = np.take_along_axis(biased, order, axis=-1)
    assert np.allclose(gap, top[:, 3] - top[:, 4], atol=1e-6)
    at_edge = np.isin(order[:, 3], [4, 5, 6, 7]) | np.isin(order[:, 4],
                                                           [4, 5, 6, 7])
    assert 0 < at_edge.sum() < len(margin)
    assert np.allclose(margin[at_edge], gap[at_edge], atol=1e-6)
    assert (margin >= gap - 1e-6).all() and np.isfinite(margin).all()
    assert (margin[~at_edge] > gap[~at_edge]).any()
    by_hand = np.min(np.where(
        biased[:, 4:8] >= top[:, 3:4], biased[:, 4:8] - top[:, 4:5],
        top[:, 3:4] - biased[:, 4:8]), axis=-1)
    assert np.allclose(margin, by_hand, atol=1e-6)
    # the weights sum to the scale over ALL the chosen, held or not
    assert np.allclose(np.asarray(dense).sum(axis=-1), 2.5, atol=1e-5)


def test_memory_account_is_the_pool_the_engine_allocates():
    """The configuration's account of what a token costs the pool, against
    ``KVCacheConfig`` as the engine and the driver size it."""
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.ragged.kv_cache import KVCacheConfig
    cfg = json.load(open(os.path.join(
        ROOT, "chipbench", "configs", "joyai-flash-serve-ep16.json")))
    layout = family().page_layout(cfg)
    assert layout == {"layers": 40, "row_values": 576, "latent_dim": 640}
    account = cfg["memory_account_numbers"]
    bs = cfg["engine"]["kv_cache"]["block_size"]
    kv = KVCacheConfig.from_memory_budget(
        layout["layers"], 0, 0, account["page_budget_bytes"], block_size=bs,
        dtype=jnp.bfloat16, latent_dim=layout["latent_dim"])
    assert kv.bytes_per_block() == account["bytes_a_page"] \
        == 40 * 128 * 640 * 2
    assert account["bytes_a_token_a_layer"] == 1280 \
        == kv.bytes_per_block() // (40 * bs)
    assert kv.num_blocks == account["pages"]
    assert account["tokens"] == account["pages"] * bs
    assert account["weight_bytes"] + account["page_budget_bytes"] \
        + cfg["hbm_headroom_bytes"] <= int(
            account["hbm_limit_bytes"] * cfg["hbm_fill"])
    # as keys of 192 and values of 128 for 32 heads the same token would
    # take 16 times the pool's row
    assert 32 * (192 + 128) * 2 / account["bytes_a_token_a_layer"] == 16


def test_published_keys_are_the_catalogs():
    """Every number of the published config under its own key; the one key
    cut is the count of experts held, with the published count beside it."""
    cfg = json.load(open(os.path.join(
        ROOT, "chipbench", "configs", "joyai-flash-serve-ep16.json")))
    want = dict(hidden_size=2048, intermediate_size=7168,
                moe_intermediate_size=768, num_hidden_layers=40,
                num_attention_heads=32, num_key_value_heads=32, head_dim=64,
                q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                qk_rope_head_dim=64, qk_head_dim=192, v_head_dim=128,
                vocab_size=129280, num_experts_per_tok=8, n_shared_experts=1,
                first_k_dense_replace=1, routed_scaling_factor=2.5,
                rope_theta=32000000, num_nextn_predict_layers=1,
                max_position_embeddings=131072, ep_size=1)
    assert {k: cfg[k] for k in want} == want
    assert cfg["reduced"] == ["n_routed_experts"]
    assert cfg["n_routed_experts"] == 16 \
        and cfg["published"] == {"n_routed_experts": 256}
    assert cfg["deployment"]["chips_sharing_a_layer"] == 16


def test_kernel_work_at_the_published_widths():
    """1,152 B and 69,632 operations a cached token a layer: 60 operations a
    byte, memory-bound on a v5e by the count."""
    from chipbench.reduce import mla_work
    assert mla_work.row_bytes(512, 64) == 1152
    assert mla_work.token_flops(32, 512, 64) == 69632
    flops, bytes_ = mla_work.decode_call([1000, 3000], 32, 512, 64)
    assert flops == 4000 * 69632
    assert bytes_ == 4000 * 1152 + 2 * 32 * (576 + 512) * 2
    peaks = json.load(open(os.path.join(ROOT, "chipbench", "peaks.json")))[
        "TPU v5 lite"]
    r = mla_work.roofline(flops, bytes_, 1e-5, peaks)
    assert r["bound"] == "memory" and 0 < r["share"] < 100
    assert r["memory_s"] == pytest.approx(bytes_ / 819e9)
    # a side slab's row is one more token a row
    f2, _ = mla_work.decode_call([1000, 3000], 32, 512, 64, side_rows=1)
    assert f2 - flops == 2 * 69632


def test_chunk_work_is_causal():
    from chipbench.reduce import mla_work
    # 4 query tokens from position 10 over a context of 14: 11+12+13+14
    flops, bytes_ = mla_work.chunk_call([(10, 4, 14)], 32, 512, 64)
    assert flops == 50 * 69632
    assert bytes_ == 14 * 1152 + 4 * 32 * (576 + 512) * 2


def test_roofline_tool_reads_the_decode_steps_kernel_calls():
    """``tools/mla_roofline.py`` on a trace written by hand: the kernel's
    call inside a decode step counts, the one inside a prefill program does
    not, and the work comes from the contexts sampled under the capture."""
    from chipbench.harness import Registry
    from chipbench.reduce import mla_work
    from tests.chipbench.test_named import hand_trace
    tool = Registry().module("tools", "mla_roofline")
    call = "jit(serve_{})/while/body/attn/mla/decode/mla_decode/pallas_call"
    op_names = {
        "jit_serve_decode_step(1)": {
            "closed_call.21": call.format("decode_step")},
        "jit_serve_prefill_packed(2)": {
            "closed_call.7": call.format("prefill_packed")}}
    peaks = json.load(open(os.path.join(ROOT, "chipbench", "peaks.json")))[
        "TPU v5 lite"]
    cfg = {"num_attention_heads": 32, "kv_lora_rank": 512,
           "qk_rope_head_dim": 64}
    view = {"trace": hand_trace(), "op_names": op_names, "peaks": peaks}
    samples = [(1, 10), (3, 70)]         # 2 rows and 40 tokens in the mean
    assert list(tool.kernel_calls(view["trace"], op_names)) == [200]
    got = tool.share_of(view, cfg, samples)
    flops, bytes_ = mla_work.decode_call([20, 20], 32, 512, 64, side_rows=1)
    assert got["calls"] == 1 and got["us_a_call"] == pytest.approx(0.2)
    assert got["flops_a_call"] == flops and got["bytes_a_call"] == bytes_
    assert got["bound"] == "memory"
    assert got["share"] == pytest.approx(100 * bytes_ / 819e9 / 200e-9)
    assert tool.share_of(view, cfg, []) == {"calls": 1}
    assert tool.share_of(dict(view, op_names={}), cfg, samples) == {}


CELL = "joyai-flash-serve-ep16.assist-closed"
#: what ``mla_row_write`` (the fused decode step's flush of its side slab)
#: is replaced with, and what of the check has to notice
FLUSH_FAULTS = {
    "sound": (None, []),
    "writes_nothing": (
        lambda real: lambda pool, side, bt, prefix, n: pool,
        ["the median of the fused path's rows, long sequence",
         "the median of the fused path's rows, short sequence"]),
    "writes_another_sequences_rows": (
        lambda real: lambda pool, side, bt, prefix, n: real(
            pool, side[:, :1].repeat(side.shape[1], axis=1), bt, prefix, n),
        ["the median of the fused path's rows, long sequence",
         "the median of the fused path's rows, short sequence"]),
}


@pytest.mark.parametrize("fault", list(FLUSH_FAULTS))
def test_the_cells_check_holds_the_fused_decode_step(fault, monkeypatch):
    """The cell's own bring-up at the rehearsal's widths. Its check runs the
    fused decode step as traffic does (two compared rows among live
    neighbours, across a page boundary) and holds the logits each run of
    steps leaves and those of a ragged pass after them: sound, it passes;
    with the step's row write broken, the fused path's rows fail it while
    the ragged passes' rows, which never met the fault, still pass."""
    import jax
    from chipbench import harness, rehearse
    from deepspeed_tpu.inference.v2 import ragged_mla
    break_it, named = FLUSH_FAULTS[fault]
    if break_it is not None:
        monkeypatch.setattr(ragged_mla, "mla_row_write",
                            break_it(ragged_mla.mla_row_write))
    said = []
    monkeypatch.setattr(harness.Context, "log",
                        lambda self, msg: said.append(msg))
    reg = harness.Registry()
    cell = reg.cell(CELL)
    config, traffic = rehearse.tiny(cell, reg.config(cell["config"]),
                                    reg.traffic(cell["traffic"]))
    ctx = harness.Context(
        registry=reg, cell=cell, config=config, traffic=traffic, seed=11,
        seconds=1.0, devices=jax.devices()[:1], peaks={},
        compiles=harness.CompileCounter(), t_process=0.0, on_chip=False)
    served = reg.module("drivers", cell["driver"]).bring_up(ctx)
    failed = [m for m in said if m.startswith("CHECK FAILED")]
    assert served.correct == (not named) and len(failed) == bool(named)
    for name in named:
        assert name in failed[0]
    assert not [m for m in failed if "of the compared rows" in m]
    # the control ran, and read over each limit
    assert not [m for m in failed if "control" in m]
    assert [m for m in said if "the control reads" in m]
