"""ZAYA1 (``zaya``) through InferenceEngineV2: compressed convolutional
attention — every layer writes pages AND keeps a convolution tail in a slot
of the state pool — over a top-1 router that is an MLP on a state handed from
layer to layer, with a choice that skips the experts, and learned scales
where a branch joins the stream. Against the plain reference
``chipbench/reference/zaya_ref.py`` through the packed pass (a chunk boundary
inside the convolutions' reach), the paged passes (tails handed through the
pool and from slot to slot), single tokens through the cache and both forms
of the fused decode step, with rows joining and leaving; the tails
themselves; what the adapter permutes; what the spec says of pools and
kinds, for this family and for the accepted ones."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from deepspeed_tpu.inference.v2 import (  # noqa: E402
    adapters, model_spec as ms, ragged_model as rm)
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2  # noqa: E402
from deepspeed_tpu.inference.v2.ragged.state_pool import (  # noqa: E402
    StatePoolConfig)
from deepspeed_tpu.models.zaya import ZayaConfig, ZayaForCausalLM  # noqa: E402

#: 2 chunk slots of 16 rows a pass (32 tokens), pages of 16, 4 decode rows
ENGINE = {"dtype": "float32",
          "state_manager": {"max_context": 256, "max_tracked_sequences": 4,
                            "max_ragged_sequence_count": 4,
                            "max_ragged_batch_size": 4 + 2 * 16,
                            "prefill_chunk_size": 16},
          "kv_cache": {"block_size": 16, "num_blocks": 64}}
#: float32 engine against the float32 reference: what is left is the order
#: of summation (the paged kernels' online softmax, the grouped products) —
#: 1e-6 here; a dropped tap, bias, mean, temperature, shift, router state or
#: skip is 1e-2 and more (tests/chipbench/test_zaya_reference.py shows each)
TOL = 2e-4
#: a sequence's tails against the reference's, rms over rms: the same sums
TOL_TAIL = 1e-5


def build(seed=0, **kw):
    """Three layers at toy widths, the heads as published (4 query heads over
    2 KV heads of 128: the paged kernels are the real ones, interpreted), 4
    experts and the skip choice behind a router MLP of width 32. Every norm's
    gain is moved off one."""
    cfg = ZayaConfig.tiny(dtype=jnp.float32, **kw)
    model = ZayaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 1000))

    def shake(path, leaf):
        names = [getattr(p, "key", "") for p in path]
        if any("norm" in n for n in names):
            return leaf + 0.2 * jax.random.normal(next(keys), leaf.shape)
        return leaf

    return cfg, model, jax.tree_util.tree_map_with_path(shake, params)


def family():
    from chipbench.harness import Registry
    return Registry().module("families", "zaya")


def as_file(cfg):
    """``cfg`` as a configuration file's keys."""
    d = {k: getattr(cfg, k) for k in family().MODEL_KEYS}
    d["rope_parameters"] = {"hybrid": {"rope_theta": cfg.rope_theta}}
    return d


def reference(cfg, params, ids, **kw):
    from chipbench.reference import zaya_ref
    fam, d = family(), as_file(cfg)
    return zaya_ref.forward_logits(fam.reference_weights(params, d),
                                   np.asarray(ids), fam.reference_hp(d), **kw)


def engine_for(model, params, **over):
    return InferenceEngineV2(model=model, model_parameters=params,
                             config={**ENGINE, **over})


def close(got, want, tol=TOL):
    return np.max(np.abs(np.asarray(got) - np.asarray(want))) \
        <= tol * np.max(np.abs(np.asarray(want)))


def as_pool(tails):
    """The reference's tails ``[L, 1, W, taps]`` as the engine gives them,
    ``[L, taps, W]``."""
    t = np.asarray(tails)
    return np.swapaxes(t.reshape(t.shape[0], -1, t.shape[-1]), 1, 2)


def tail_err(got, want):
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


@pytest.fixture(scope="module")
def built():
    return build()


def run_one(cfg, model, params):
    """One engine run of one sequence: a packed pass (two slots, the second
    short: the tail goes from slot to slot, mode 2 after mode 0), paged
    passes (the tail handed through the pool, mode 1, and on to the next
    slot), four single tokens, 24 fused decode steps (the context crosses a
    page at 112), a forced token through the ragged pass; the reference then
    runs over the prompt and the engine's own tokens."""
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 256, 100).astype(np.int32)
    eng = engine_for(model, params)
    got = {"packed": eng.put([1], [prompt[:27]])[0],
           "paged": eng.put([1], [prompt[27:96]])[0]}
    for i in range(96, 100):
        got[f"single_{i}"] = eng.put([1], [prompt[i:i + 1]])[0]
    toks = eng.decode_pipeline([1]).run(24)[0]
    last = np.asarray([7], np.int32)
    got["after_24_fused"] = eng.put([1], [last])[0]
    ids = np.concatenate([prompt, toks, last])
    tails = eng.sequence_state(1)
    want, want_tails = reference(cfg, params, ids, with_state=True)
    rows = {"packed": 26, "paged": 95, "after_24_fused": len(ids) - 1,
            **{f"single_{i}": i for i in range(96, 100)}}
    return (eng, got, np.asarray(want), rows, toks, tails,
            np.asarray(want_tails))


@pytest.fixture(scope="module")
def served(built):
    return run_one(*built)


ROWS = ["packed", "paged", "single_96", "single_97", "single_98",
        "single_99", "after_24_fused"]


@pytest.mark.parametrize("row", ROWS)
def test_logits_are_the_references(served, row):
    eng, got, want, rows, *_ = served
    assert rm.side_buffer_fits(eng.spec, 1, False, None)
    assert close(got[row], want[rows[row]]), row


def test_fused_steps_choose_the_references_tokens(served):
    """Greedy tokens of the 24 fused steps (the side-buffer form: heads of
    128): the reference's argmax at each position, given the engine's own
    tokens before it."""
    _, _, want, _, toks, *_ = served
    assert (np.argmax(want[99:99 + 24], axis=-1) == toks).all()


@pytest.mark.parametrize("layer", range(3))
def test_the_tail_is_the_references(served, layer):
    """After chunk slots, passes, single tokens and 24 fused steps each
    layer's slot holds ``[s ; z]`` of the sequence's last two tokens, in the
    adapter's channel order."""
    *_, tails, want_tails = served
    assert tails.shape == (3, 2, 6 * 128 + 128)
    assert tail_err(tails[layer], as_pool(want_tails)[layer]) < TOL_TAIL


@pytest.fixture(scope="module")
def small_heads():
    return run_one(*build(seed=2, head_dim=64))


@pytest.mark.parametrize("row", ROWS)
def test_the_step_that_writes_in_the_layer_too(small_heads, row):
    """Heads of 64 are turned away by the side buffer: the decode step is the
    form whose attention kernel writes each layer's rows, and the tail's
    shift is the layer's own there as well."""
    eng, got, want, rows, toks, tails, want_tails = small_heads
    assert not rm.side_buffer_fits(eng.spec, 1, False, None)
    assert close(got[row], want[rows[row]]), row
    assert (np.argmax(want[99:99 + 24], axis=-1) == toks).all()
    assert tail_err(tails, as_pool(want_tails)) < TOL_TAIL


def test_rows_join_and_leave(built):
    """Three sequences of different lengths decode side by side; one is
    flushed and a fourth joins in its slot while the others go on: each
    one's logits and first layer's tail stay those of the reference run on
    that sequence alone (what a freed slot held does not show)."""
    cfg, model, params = built
    rng = np.random.default_rng(3)
    prompts = {u: rng.integers(0, 256, n).astype(np.int32)
               for u, n in ((1, 40), (2, 9), (3, 21), (4, 33))}
    eng = engine_for(model, params)
    eng.put([1, 2, 3], [prompts[u] for u in (1, 2, 3)])
    toks = {u: list(t) for u, t in zip(
        (1, 2, 3), eng.decode_pipeline([1, 2, 3]).run(5))}
    freed = eng.scheduler.seqs[2].state_slot
    eng.flush([2])
    eng.put([4], [prompts[4]])
    assert eng.scheduler.seqs[4].state_slot == freed
    more = eng.decode_pipeline([3, 4, 1]).run(6)
    toks[4] = []
    for u, t in zip((3, 4, 1), more):
        toks[u].extend(t)
    probe = np.asarray([11], np.int32)
    last = dict(zip((1, 3, 4), eng.put([1, 3, 4], [probe] * 3)))
    for u in (1, 3, 4):
        ids = np.concatenate([prompts[u], toks[u], probe]).astype(np.int32)
        want, tails = reference(cfg, params, ids, with_state=True)
        assert close(last[u], np.asarray(want)[-1]), u
        assert tail_err(eng.sequence_state(u), as_pool(tails)) < TOL_TAIL, u


def test_a_layer_where_every_token_skips(built):
    """The second layer's balancing bias pushed onto the skip choice: no
    token of it reads an expert, the branch is zero (the layer keeps ``a2 *
    x' + c2 + c3``), and the engine is still the reference."""
    cfg, model, params = built
    beta = params["layers_1"]["mlp"]["balancing_bias"]
    forced = {**params, "layers_1": {**params["layers_1"], "mlp": {
        **params["layers_1"]["mlp"], "balancing_bias": beta.at[-1].set(9.0)}}}
    ids = np.random.default_rng(5).integers(0, 256, 40).astype(np.int32)
    eng = engine_for(model, forced)
    got = eng.put([1], [ids])[0]
    want = np.asarray(reference(cfg, forced, ids))
    assert close(got, want[-1])
    assert not close(want[-1], np.asarray(reference(cfg, params, ids))[-1],
                     1e-2)


def test_the_skip_choice_visits_no_expert():
    """Rows whose choice is ``num_experts`` sort past the groups: their
    output is exactly zero whatever the experts hold, the others' is their
    expert's times the weight."""
    rng = np.random.default_rng(0)
    E, hid, F = 4, 128, 128
    x = jnp.asarray(rng.standard_normal((8, hid)), jnp.float32)
    w = {k: jnp.asarray(rng.standard_normal(s) * 0.1, jnp.float32)
         for k, s in (("w_gate", (E, hid, F)), ("w_up", (E, hid, F)),
                      ("w_down", (E, F, hid)))}
    ids = jnp.asarray([[0], [4], [3], [4], [1], [2], [4], [0]], jnp.int32)
    gates = jnp.full((8, 1), 0.25, jnp.float32)
    routing = {"num_experts": E, "top_k": 1, "router": "mlp", "skip": True}
    out = np.asarray(rm._moe_ffn(x, w, 1, jnp.float32, routing=routing,
                                 routed=(gates, ids)))
    skipped = np.asarray(ids[:, 0] == E)
    assert (out[skipped] == 0).all() and (np.abs(out[~skipped]) > 0).any()
    e = 3
    want = 0.25 * rm._swiglu(x[2:3], {k: v[e] for k, v in w.items()})
    assert close(out[2:3], want, 1e-5)


def router_weights(rng, hid=128, R=32, E=4):
    shapes = {"router_down": (hid, R), "router_down_b": (R,),
              "router_gamma": (R,), "router_norm": (R,),
              "router_fc1": (R, R), "router_fc1_b": (R,),
              "router_fc2": (R, R), "router_fc2_b": (R,),
              "router_out": (R, E + 1), "router_bias": (E + 1,)}
    return {k: jnp.asarray(rng.standard_normal(s) * (0.02 if k ==
                           "router_bias" else 0.3), jnp.float32)
            for k, s in shapes.items()}


def test_a_router_with_no_scale_is_handed_no_state():
    """``gamma = 0`` (the first layer's, as the adapter stores it): whatever
    state comes in, the choice, the weight and the state that goes out are
    those of a router handed zeros; with ``gamma`` they move."""
    rng = np.random.default_rng(1)
    w = router_weights(rng)
    routing = {"num_experts": 4, "top_k": 1, "router": "mlp", "skip": True,
               "router_hidden": 32}
    x = jnp.asarray(rng.standard_normal((64, 128)), jnp.float32)
    r_in = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    none = rm.moe_route_mlp(x, w, routing, jnp.zeros_like(r_in), 1e-5)
    off = rm.moe_route_mlp(x, {**w, "router_gamma": jnp.zeros((32,))},
                           routing, r_in, 1e-5)
    on = rm.moe_route_mlp(x, w, routing, r_in, 1e-5)
    for a, b in zip(none, off):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert not np.allclose(np.asarray(none[2]), np.asarray(on[2]))
    assert (np.asarray(on[1]) <= 4).all() and on[1].shape == (64, 1)
    # the weight is the chosen probability as it is: under 1, not renormalised
    assert (np.asarray(on[0]) < 1).all() and (np.asarray(on[0]) > 1 / 5).all()


def test_the_adapters_permutation_leaves_every_q_dot_k(built):
    """``adapt_zaya`` interleaves the rotated channels of each q and k head
    in the projections and in both convolutions: q and k out of the
    program's projection are the reference's with their channels in that
    order, so every ``q . k`` is as it was, and v is untouched."""
    from chipbench.reference import zaya_ref
    cfg, model, params = built
    spec, weights = adapters.adapt_zaya(params, cfg)
    T, L = 21, cfg.num_hidden_layers
    u = jnp.asarray(np.random.default_rng(2).standard_normal(
        (T, cfg.hidden_size)), jnp.float32)
    pool = StatePoolConfig.tails_only(L, 2, spec.cca["taps"],
                                      spec.cca["tail_channels"])
    conv = pool.zeros()[1]
    rows = rm._StateRows(chunk_slot=jnp.zeros((1,), jnp.int32),
                         chunk_mode=jnp.zeros((1,), jnp.int32),
                         chunk_ntok=jnp.full((1,), T, jnp.int32))
    layer = 1
    w = jax.tree_util.tree_map(lambda a: a[layer], weights["layers"])
    q, k, v, conv = rm._cca_project(spec, w, u, jnp.arange(T), conv, layer,
                                    rows)
    fam, d = family(), as_file(cfg)
    ref_w = fam.reference_weights(params, d)["layers"][layer]
    with jax.default_matmul_precision("highest"):
        rq, rk, rv, tail = zaya_ref.cca_qkv(
            u, ref_w, fam.reference_hp(d), lambda x: x, lambda x: x)
    turn = adapters.zaya.zaya_channel_order(1, cfg.head_dim, cfg.rotary_dim)
    assert sorted(turn[:cfg.rotary_dim]) == list(range(cfg.rotary_dim))
    assert (turn[:4] == [0, cfg.rotary_dim // 2, 1,
                         cfg.rotary_dim // 2 + 1]).all()
    assert close(q, np.asarray(rq)[..., turn], 1e-5)
    assert close(k, np.asarray(rk)[..., turn], 1e-5)
    assert close(v, rv, 1e-5)
    dots = lambda q, k: np.einsum("thd,sgd->thgs", np.asarray(q),
                                  np.asarray(k))
    assert close(dots(q, k), dots(rq, rk), 1e-5)
    # and the slot written is this layer's, in the adapter's channel order
    W = spec.cca["tail_channels"]
    got = np.asarray(conv)[layer, 0].reshape(spec.cca["taps"], -1)[:, :W]
    assert close(got, np.asarray(tail).T[:, np.asarray(
        fam.reference_hp(d)["tail_order"])], 1e-5)
    assert not np.asarray(conv)[layer - 1].any()


def test_every_layer_addresses_both_pools(built):
    cfg, model, params = built
    spec, _ = adapters.adapt_zaya(params, cfg)
    L = cfg.num_hidden_layers
    assert spec.layer_kinds is None and spec.cca["taps"] == 2
    assert ms._layer_holds(spec) == ["both"] * L
    assert ms.num_page_layers(spec) == ms.num_state_layers(spec) == L
    everyone = list(range(L))
    assert ms._pool_index(spec) == ms._pool_index(spec, "pages") \
        == ms._pool_index(spec, "state") == everyone
    assert ms._pool_bases(spec) == [0]
    assert ms._holds(ms.CcaKind()) == "both"
    assert "pages and a convolution tail" in ms.describe_layer_kinds(spec)


A, W = ms.LayerKind(None, False, False), ms.LayerKind(64, True, True)
M, D, ME = ms.MambaKind(), ms.DeltaKind(True), ms.MambaKind(True)
BM, BA, BE = (ms.BlockKind("mamba"), ms.BlockKind("attention"),
              ms.BlockKind("moe"))


@pytest.mark.parametrize("kinds, holds, index, pages, state", [
    # one kind, the scalar fields: llama / mistral, mixtral, joyai
    (None, ["pages"] * 4, [0, 1, 2, 3], 4, 0),
    # afmoe: windowed and full attention layers
    ((W, W, W, A), ["pages"] * 4, [0, 1, 2, 3], 4, 0),
    # jamba: Mamba-1 beside attention
    ((M, M, A, M), ["state", "state", "pages", "state"], [0, 1, 0, 2], 1, 3),
    # granite: Mamba-2 over experts beside attention
    ((ME, ME, ms.LayerKind(None, False, True), ME),
     ["state", "state", "pages", "state"], [0, 1, 0, 2], 1, 3),
    # nemotron_h: one block a layer
    ((BM, BE, BM, BA, BE, BM, BE),
     ["state", None, "state", "pages", None, "state", None],
     [0, 0, 1, 0, 1, 2, 2], 1, 3),
    # qwen3_next: three delta layers and an attention layer, twice
    ((D, D, D, ms.LayerKind(None, True, True)) * 2,
     ["state"] * 3 + ["pages"] + ["state"] * 3 + ["pages"],
     [0, 1, 2, 0, 3, 4, 5, 1], 2, 6),
])
def test_the_accepted_families_pools_are_as_they_were(kinds, holds, index,
                                                      pages, state):
    """What a layer of each accepted family addresses, and where, is what it
    was before a layer could address both pools."""
    n = 4 if kinds is None else len(kinds)
    spec = ms.RaggedModelSpec("x", n, 128, 4, 2, 32, 256, layer_kinds=kinds,
                              mamba={"d_inner": 8} if state else None)
    assert ms._layer_holds(spec) == holds
    assert ms._pool_index(spec) == index
    assert (ms.num_page_layers(spec), ms.num_state_layers(spec)) == (pages,
                                                                     state)
    for pool in ("pages", "state"):
        ranks = [i for i, h in zip(ms._pool_index(spec, pool), holds)
                 if h == pool]
        assert ranks == list(range(len(ranks)))


def test_a_tail_beside_layers_of_one_pool_is_refused():
    """The layer loop hands a layer ONE index: a model that mixes layers
    that keep a tail beside their pages with layers of one pool says so."""
    kinds = (ms.MambaKind(), ms.CcaKind(), ms.CcaKind())
    spec = ms.RaggedModelSpec("x", 3, 128, 4, 2, 128, 256, layer_kinds=kinds,
                              mamba={"d_inner": 8}, cca={"taps": 2})
    assert ms._pool_index(spec) == [0, 0, 1]
    assert ms._pool_index(spec, "state") == [0, 1, 2]
    assert ms._pool_index(spec, "pages") == [0, 0, 1]
    assert (ms.num_page_layers(spec), ms.num_state_layers(spec)) == (2, 3)
    with pytest.raises(NotImplementedError, match="one index"):
        rm._scan_layers(spec, ({}, {}), None, ())


@pytest.mark.parametrize("over", [{"prefix_cache": {"enabled": True}},
                                  {"spec_decode": {"enabled": True}}],
                         ids=["prefix_cache", "spec_decode"])
def test_what_is_refused_beside_the_tail(built, over):
    """Snapshots of the tail are refused as beside Mamba layers."""
    cfg, model, params = built
    with pytest.raises(NotImplementedError, match="state"):
        engine_for(model, params, **over)


def test_what_the_served_engine_refuses_and_reports(served):
    """Pages may not leave without the tail (export_kv, offload), no verify
    step is built; the engine's always-on values say what it is."""
    from deepspeed_tpu.inference.v2.serving.frontend import ServingFrontend
    from deepspeed_tpu.monitor.trace import tracer
    eng = served[0]
    with pytest.raises(NotImplementedError, match="state"):
        eng.export_kv(1)
    with pytest.raises(NotImplementedError, match="state"):
        ServingFrontend(eng, {"preemption": "offload"})
    with pytest.raises(NotImplementedError, match="state"):
        rm.build_verify_step(eng.spec, 2)
    sc = eng.state_config
    assert (sc.num_layers, sc.d_inner, sc.d_state, sc.d_conv, sc.conv_dim,
            sc.conv_width) == (3, 0, 0, 3, 896, 1024)
    assert sc.bytes_per_slot() == 3 * 4 * 2 * 1024
    assert eng.kv.kv.ssm.size == 0 and eng.kv.config.num_layers == 3
    totals = tracer.totals
    assert totals["serve/state/kind"] == 4
    assert totals["serve/moe/router_kind"] == 2
    assert totals["serve/cca/tail_channels"] == 896
    assert totals["serve/state/bytes_per_sequence"] == sc.bytes_per_slot()
    cfg_file = as_file(ZayaConfig.tiny())
    assert family().check_engine(cfg_file, eng) == ""


def test_the_frontend_serves_it(built):
    """Through ServingFrontend, the scheduler and the slot allocator: three
    requests of different lengths, greedy, give the tokens the engine's own
    pipeline gives each prompt alone; every slot is given back."""
    cfg, model, params = built
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (5, 37, 18)]
    eng = engine_for(model, params, serving={"preemption": "none"})
    alone = []
    for p in prompts:
        eng.put([1], [p])
        alone.append([int(t) for t in eng.decode_pipeline([1]).run(6)[0]])
        eng.flush([1])
    with eng.serving_frontend() as frontend:
        handles = [frontend.submit(p, max_new_tokens=6) for p in prompts]
        frontend.drain()
        got = [[int(t) for t in h.tokens][:6] for h in handles]
    assert got == alone
    assert eng.state_slots()[0] == 0


def test_the_scopes_the_metrics_read_are_in_the_step(built):
    """The decode step's operations carry the scopes the cell's per-layer
    metrics read (``chipbench/layer_metrics/cca_*.json``,
    ``moe_router_share.reason64.json``)."""
    cfg, model, params = built
    spec, weights = adapters.adapt_zaya(params, cfg)
    pool = StatePoolConfig.tails_only(3, 4, 2, spec.cca["tail_channels"])
    from deepspeed_tpu.inference.v2.ragged.state_pool import StatefulKV
    kv = StatefulKV(jnp.zeros((3, 9, 2, 2, 16, 128), jnp.float32),
                    *pool.zeros())
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    step = rm.build_decode_step(spec)
    text = jax.jit(step).lower(
        weights, kv, i32(4), i32(4), i32(4, 16), i32(4) + 1,
        jax.random.PRNGKey(0), jnp.float32(1.0), i32(4)).as_text(
            debug_info=True)
    for scope in ("attn/cca/proj", "attn/cca/mix", "attn/cca/attn_full",
                  "attn/cca/out", "ffn/moe_ffn/router/mlp",
                  "ffn/moe_ffn/experts", "kv_flush"):
        assert scope in text, scope
    # the router's four products are asked for at the highest precision: the
    # chip's default would run them in one bfloat16 pass
    assert text.count("precision = [HIGHEST, HIGHEST]") == 4
