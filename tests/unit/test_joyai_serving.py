"""JoyAI-LLM-Flash through InferenceEngineV2: latent attention (MLA) over a
pool of latent rows with no head axis — expanded in the packed prefill,
absorbed wherever the pool is read — and a sigmoid router over experts of
which an engine may hold one chip's share; against the plain reference
``chipbench/reference/joyai_ref.py``, logits and not tokens, through the
packed pass, paged chunk passes, single tokens through the cache and the
fused decode step (one pipeline run, and two); the two forms on one cache; the
latent row's bytes; the shares of an expert layer adding up; and what is
refused beside latent pages."""

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
    adapters, model_spec as ms, ragged_mla, ragged_model as rm)
from deepspeed_tpu.inference.v2.attention import AttentionKernelSpec  # noqa: E402
from deepspeed_tpu.inference.v2.config_v2 import (  # noqa: E402
    RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2  # noqa: E402
from deepspeed_tpu.inference.v2.ragged.kv_cache import KVCacheConfig  # noqa: E402
from deepspeed_tpu.models.joyai import JoyaiConfig, JoyaiForCausalLM  # noqa: E402
from deepspeed_tpu.monitor.trace import tracer  # noqa: E402

#: 2 chunk slots of 16 rows a pass (32 tokens), pages of 16, 4 decode rows
ENGINE = {"dtype": "float32",
          "state_manager": {"max_context": 256, "max_tracked_sequences": 4,
                            "max_ragged_sequence_count": 4,
                            "max_ragged_batch_size": 4 + 2 * 16,
                            "prefill_chunk_size": 16},
          "kv_cache": {"block_size": 16, "num_blocks": 64}}
#: float32 engine against the float32 reference: what is left is the order
#: of summation (online softmax by page, the absorbed products' association),
#: a few float32 ulps through four layers. A dropped norm, an unrotated key
#: or another scale is 1e-2 and more (tests/chipbench/test_joyai_reference.py)
TOL = 2e-4
#: the engine holds all 16 experts of the tiny model, or experts 4-7 of them
SHARES = {"all": None, "held": (4, 4)}


def build(share="all", seed=0, **kw):
    cfg = JoyaiConfig.tiny(dtype=jnp.float32, experts_held=SHARES[share],
                           **kw)
    model = JoyaiForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, model, params


def family():
    from chipbench.harness import Registry
    return Registry().module("families", "joyai")


def file_keys(cfg):
    """``cfg`` as a configuration file spells it: ``n_routed_experts`` counts
    the experts held, ``published`` the router's width."""
    fam = family()
    d = {k: getattr(cfg, k) for k in fam.MODEL_KEYS}
    first, count = cfg.held
    return dict(d, n_routed_experts=count,
                published={"n_routed_experts": cfg.n_routed_experts},
                deployment={"held_first": first})


def reference(cfg, params, ids, **kw):
    from chipbench.reference import joyai_ref
    fam, d = family(), file_keys(cfg)
    return joyai_ref.forward_logits(fam.reference_weights(params, d),
                                    jnp.asarray(ids), fam.reference_hp(d),
                                    **kw)


def engine_for(model, params, **over):
    return InferenceEngineV2(model=model, model_parameters=params,
                             config={**ENGINE, **over})


def err(got, want):
    return float(np.max(np.abs(np.asarray(got) - np.asarray(want)))
                 / np.max(np.abs(np.asarray(want))))


def last_logits(eng, uid):
    """The logits the last fused step left for ``uid`` (what the next
    sample reads)."""
    eng._materialize([uid])
    return eng._last_logits[uid]


IDS = np.random.default_rng(1).integers(0, 256, size=80).astype(np.int32)
#: the fused steps run from a prompt of 40 tokens; 12 steps cross the page
#: boundary at 48
FUSED_FROM, FUSED = 40, 12


@pytest.fixture(scope="module", params=list(SHARES))
def served(request):
    """One engine a share: a prompt through the packed pass (32 tokens: two
    chunk slots), paged chunk passes (28 more, the last slot part filled),
    four single tokens through the cache; then, as other sequences, the
    fused decode step on its own greedy tokens: one pipeline run of the
    twelve steps, and two runs (5 and 7: the second reserves anew and goes
    on from the rows the first wrote)."""
    cfg, model, params = build(request.param)
    eng = engine_for(model, params)
    got = {"packed": (eng.put([1], [IDS[:32]])[0], 31),
           "paged": (eng.put([1], [IDS[32:60]])[0], 59)}
    for i in range(60, 64):
        got[f"single_{i}"] = (eng.put([1], [IDS[i:i + 1]])[0], i)
    want = np.asarray(reference(cfg, params, IDS[:64]))
    eng.flush([1])
    out = {k: (np.asarray(v), want[row]) for k, (v, row) in got.items()}
    for name, uid, run in (
            ("fused", 2, lambda: eng.decode_pipeline([2]).run(FUSED)[0]),
            ("fused_two_runs", 3, lambda: np.concatenate(
                [eng.decode_pipeline([3]).run(n)[0]
                 for n in (5, FUSED - 5)]))):
        eng.put([uid], [IDS[:FUSED_FROM]])
        toks = np.asarray(run(), np.int32)
        logits = last_logits(eng, uid)
        eng.flush([uid])
        # the steps consumed toks[0..n-1] (toks[0] the prompt's own next
        # token); the logits left predict the token after toks[-1]
        seq = np.concatenate([IDS[:FUSED_FROM], toks])
        ref = np.asarray(reference(cfg, params, seq))
        out[name] = (logits, ref[len(seq) - 1])
        out[name + "_tokens"] = (toks, np.argmax(
            ref[FUSED_FROM - 1:len(seq) - 1], axis=-1))
    return out


@pytest.mark.parametrize("phase", ["packed", "paged", "single_60",
                                   "single_61", "single_62", "single_63",
                                   "fused", "fused_two_runs"])
def test_engine_logits_match_the_reference(served, phase):
    got, want = served[phase]
    assert np.isfinite(got).all() and err(got, want) <= TOL, err(got, want)


@pytest.mark.parametrize("loop", ["fused", "fused_two_runs"])
def test_decode_through_a_page_boundary_chooses_the_reference_tokens(
        served, loop):
    """Twelve steps from position 40: the side buffer's rows land in two
    pages (the boundary at 48), each step reading the rows before it."""
    got, want = served[loop + "_tokens"]
    assert list(got) == list(want)


def test_absorbed_equals_expanded_on_the_same_cache():
    """One pool of latent rows, one set of queries: the absorbed form (the
    kernel over the pages, ``W_UK`` in the queries, ``W_UV`` on the output)
    against the expanded form computed from the same rows in plain jnp (keys
    and values of every head made from each cached latent)."""
    cfg, _, params = build()
    spec, weights = adapters.adapt_joyai(params, cfg)
    w = jax.tree_util.tree_map(lambda a: a[0], weights["layers"][1])
    m, H = spec.mla, spec.num_heads
    R, dn, dr, dv = (m["kv_lora_rank"], m["qk_nope_head_dim"],
                     m["qk_rope_head_dim"], m["v_head_dim"])
    W, bs, NB, S = ms.latent_width(spec), 16, 12, 3
    rng = np.random.default_rng(3)
    rows = rng.standard_normal((NB, bs, W)).astype(np.float32)
    rows[..., R + dr:] = 0
    pool = jnp.asarray(rows)
    bt = jnp.asarray(rng.permutation(NB)[:S * 4].reshape(S, 4), jnp.int32)
    ctx = jnp.asarray([5, 33, 64], jnp.int32)
    q_nope = jnp.asarray(rng.standard_normal((S, H, dn)), jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal((S, H, dr)), jnp.float32)
    ak = AttentionKernelSpec(ms.layer_runs(spec)[1][0])
    o_lat = ak.latent(ragged_mla.mla_absorb_q(spec, w, q_nope, q_rope, W),
                      pool, bt, ctx - 1, ctx)
    got = np.asarray(ragged_mla.mla_absorb_o(w, o_lat)).reshape(S, H, dv)
    for s in range(S):
        lat = pool[bt[s]].reshape(-1, W)[:int(ctx[s])]
        k = jnp.concatenate(
            [jnp.einsum("tr,hrd->thd", lat[:, :R], w["w_uk"]),
             jnp.broadcast_to(lat[:, None, R:R + dr],
                              (lat.shape[0], H, dr))], axis=-1)
        v = jnp.einsum("tr,hrd->thd", lat[:, :R], w["w_uv"])
        q = jnp.concatenate([q_nope[s], q_rope[s]], axis=-1)
        p = jax.nn.softmax(jnp.einsum("hd,thd->ht", q, k)
                           * (dn + dr) ** -0.5, axis=-1)
        want = np.asarray(jnp.einsum("ht,thd->hd", p, v))
        assert err(got[s], want) <= 1e-5


# --------------------------------------------------------------------------- #
# the pool: one latent row a token a layer
# --------------------------------------------------------------------------- #

def test_latent_pool_is_one_row_a_token_a_layer():
    """At the published widths a token costs a layer 640 bfloat16 values
    (512 + 64 padded to whole lane tiles) = 1,280 B: not the latent twice,
    not keys and values per head (20,480 B)."""
    cfg = JoyaiConfig.joyai_llm_flash()
    spec = ms.RaggedModelSpec(
        family="joyai", num_layers=40, hidden_size=2048, num_heads=32,
        num_kv_heads=32, head_dim=128, vocab_size=129280,
        mla={"q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
             "qk_nope_head_dim": cfg.qk_nope_head_dim,
             "qk_rope_head_dim": cfg.qk_rope_head_dim,
             "v_head_dim": cfg.v_head_dim})
    assert ms.latent_width(spec) == 640
    kv = KVCacheConfig(40, 32, 128, 128, 10, jnp.bfloat16,
                       latent_dim=ms.latent_width(spec))
    assert kv.page_shape == (40, 128, 640)
    per_token_layer = kv.bytes_per_block() / (40 * 128)
    assert 576 * 2 <= per_token_layer <= 1280
    sized = KVCacheConfig.from_memory_budget(
        40, 32, 128, 10 * kv.bytes_per_block() + 5, 128, jnp.bfloat16,
        latent_dim=640)
    assert sized.num_blocks == 10 and sized.latent_dim == 640


def test_engine_pool_has_no_head_axis_and_says_what_a_token_costs(served):
    del served          # (an engine has been built: the values are set)
    _, model, params = build("held")
    eng = engine_for(model, params)
    L, NB1, bs, W = eng.kv.kv.shape
    assert (L, NB1, bs, W) == (4, 65, 16, 128)      # 64 + 16 values -> 128
    assert eng.kv.kv.nbytes == eng.kv.config.bytes_per_block() * NB1
    assert eng.page_payload_spec[0] == (4, 16, 128)
    assert tracer.totals["serve/latent/bytes_per_token"] == 128 * 4
    assert tracer.totals["serve/moe/held_experts"] == 4


def test_adapter_reads_the_published_tree_and_skips_the_mtp_module():
    cfg, _, params = build("held")
    extra = dict(params, layers_4=params["layers_3"])    # the MTP module
    spec, weights = adapters.adapt_joyai(extra, cfg)
    assert spec.num_layers == 4 and [n for _, _, n in ms.layer_runs(spec)] \
        == [1, 3]
    assert spec.moe["num_experts"] == 16 and spec.moe["held"] == (4, 4)
    dense, sparse = weights["layers"]
    assert "mlp" in dense and "moe" not in dense
    assert sparse["moe"]["router"].shape == (3, 64, 16)
    assert sparse["moe"]["w_gate"].shape == (3, 4, 64, 32)
    assert sparse["w_uk"].shape == (3, 4, 64, 32)       # [L, H, R, nope]
    full, _ = adapters.adapt_joyai(params, build()[0])
    assert "held" not in full.moe


# --------------------------------------------------------------------------- #
# one chip's share of the experts
# --------------------------------------------------------------------------- #

def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four engines' shares of 16 experts: the routed parts they compute,
    plus the shared expert once, are the uncut reference's MoE layer."""
    from chipbench.reference import joyai_ref
    cfg, _, params = build()
    fam, d = family(), file_keys(cfg)
    layer = fam.reference_weights(params, d)["layers"][2]
    hp = fam.reference_hp(d)
    spec, weights = adapters.adapt_joyai(params, cfg)
    w = jax.tree_util.tree_map(lambda a: a[1], weights["layers"][1]["moe"])
    x = jnp.asarray(np.random.default_rng(5).standard_normal((24, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = joyai_ref.sparse_mixture(x, layer, hp)
        shared = rm._swiglu(x, w["shared"])
        routed = {k: v for k, v in w.items() if k != "shared"}
        parts = []
        for first in range(0, 16, 4):
            mine = dict(routed, **{k: routed[k][first:first + 4]
                                   for k in ("w_gate", "w_up", "w_down")})
            parts.append(rm._moe_ffn(
                x, mine, 4, jnp.float32,
                routing=dict(spec.moe, held=(first, 4))))
        whole = rm._moe_ffn(x, routed, 4, jnp.float32, routing=spec.moe)
    assert err(sum(parts) + shared, want) <= 1e-5
    assert err(whole + shared, want) <= 1e-5
    # a share is not the whole: each one alone is far off
    assert all(err(p + shared, want) > 1e-2 for p in parts)


def _parent_moe_ffn(x, w, top_k, dtype, l=0, routing=None):
    """``_moe_ffn`` as the parent commit had it (12336e8), word for word but
    for the scopes' decorator: what a model holding all its experts must
    still lower to."""
    T, hid = x.shape
    E = w["router"].shape[-1]
    with jax.named_scope("router"):
        gates, ids = rm.moe_route(x, w, top_k, routing)

    with jax.named_scope("sort"):
        tok_idx = jnp.repeat(jnp.arange(T), top_k)
        expert_ids = ids.reshape(-1)
        order = jnp.argsort(expert_ids)
        rows = jnp.pad(order, (0, -order.shape[0] % 8))
        xs = x[tok_idx[rows]]
        group_sizes = jnp.bincount(expert_ids, length=E).astype(jnp.int32)

    def gg(lhs, rhs):
        groups = rhs.reshape((-1,) + rhs.shape[-2:])
        sizes = group_sizes
        if groups.shape[0] != E:
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros(groups.shape[0], jnp.int32), group_sizes, (l * E,))
        return jax.lax.ragged_dot(lhs, groups.astype(lhs.dtype), sizes)

    with jax.named_scope("experts"):
        h = jax.nn.silu(gg(xs, w["w_gate"])) * gg(xs, w["w_up"])
        ys = gg(h, w["w_down"])[:order.shape[0]]
    with jax.named_scope("combine"):
        scale = gates.reshape(-1)[order].astype(ys.dtype)
        inv = jnp.argsort(order)
        out = (ys * scale[:, None])[inv].reshape(T, top_k, hid).sum(axis=1)
    if "shared" in w:
        with jax.named_scope("shared"):
            out = out + rm._swiglu(x, w["shared"])
    return out.astype(dtype)


@pytest.mark.parametrize("router", ["softmax_top2", "sigmoid_bias_shared"])
def test_a_layer_holding_all_its_experts_lowers_to_the_parents_text(router):
    """Mixtral's router and Trinity's (sigmoid, selection bias, shared
    expert), the expert stacks whole ``[L, E, K, N]`` with the layer a traced
    index: without ``held`` the MoE layer's lowered text is the parent's."""
    E, hid, F, L = 8, 64, 32, 3
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    w = {"router": f32(hid, E), "w_gate": f32(L, E, hid, F),
         "w_up": f32(L, E, hid, F), "w_down": f32(L, E, F, hid)}
    routing = {"num_experts": E, "top_k": 2}
    if router != "softmax_top2":
        routing.update(score_func="sigmoid", route_norm=True,
                       route_scale=2.826)
        w.update(expert_bias=f32(E), shared={
            "w_gate": f32(hid, F), "w_up": f32(hid, F), "w_down": f32(F, hid)})

    def text(fn):
        def moe_layer(x, w, l):
            with jax.named_scope("moe_ffn"):
                return fn(x, w, 2, jnp.float32, l, routing=routing)
        return jax.jit(moe_layer).lower(
            f32(20, hid), w, jax.ShapeDtypeStruct((), jnp.int32)).as_text()

    assert text(rm._moe_ffn.__wrapped__) == text(_parent_moe_ffn)


# --------------------------------------------------------------------------- #
# what reads pages by shape: carried, or refused by name
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("over,says", [
    ({"kv_quant": {"enabled": True}}, r"kv_quant\.enabled"),
    ({"tensor_parallel": 2}, r"tensor_parallel > 1"),
    ({"attention": {"decode_splits": 2}}, r"attention\.decode_splits > 1"),
    ({"lora": {"enabled": True}}, r"lora\.enabled"),
    ({"quantization": {"weight_bits": 8}}, r"quantization\.weight_bits")])
def test_engine_build_refuses_beside_latent_pages(over, says):
    cfg, _, params = build()
    spec, _ = adapters.adapt_joyai(params, cfg)
    config = RaggedInferenceEngineConfig.load({**ENGINE, **over})
    with pytest.raises(NotImplementedError, match=says) as e:
        AttentionKernelSpec.validate_engine_build(spec, config)
    assert "one latent row a token a layer" in str(e.value)
    AttentionKernelSpec.validate_engine_build(
        spec, RaggedInferenceEngineConfig.load(ENGINE))


def test_prefix_cache_hands_latent_pages_to_the_next_prompt():
    """Pages move by the page axis, whatever a page holds: a second prompt
    that shares 32 tokens (two pages) with a flushed one gets the logits of
    a cold run."""
    cfg, model, params = build("held")
    want = np.asarray(reference(cfg, params, IDS[:50]))[49]
    eng = engine_for(model, params, prefix_cache={"enabled": True})
    eng.put([1], [IDS[:40]])
    eng.flush([1])
    got = eng.put([2], [IDS[:50]])[0]
    assert eng.prefix_cache.stats.tokens_saved >= 32
    assert err(got, want) <= TOL


def test_speculative_verify_gives_the_greedy_stream():
    """The verify step over latent pages (k + 1 rows a sequence written,
    then attended absorbed and causal): the stream equals the decode
    step's."""
    cfg, model, params = build("held")
    prompt = np.tile(IDS[:10], 4)          # repeats: the n-gram drafts hit
    plain = engine_for(model, params)
    plain.put([1], [prompt])
    want = [int(t) for t in plain.decode_pipeline([1]).run(16)[0]]
    eng = engine_for(model, params, spec_decode={"enabled": True})
    eng.put([1], [prompt])
    # (a verify step emits its accepted drafts too: at least 16 tokens)
    got = [int(t) for t in eng.decode_pipeline([1]).run(16)[0]]
    assert got[:16] == want and eng.spec_stats.accepted > 0


def test_export_and_import_move_latent_pages_between_engines():
    cfg, model, params = build("held")
    a, b = engine_for(model, params), engine_for(model, params)
    a.put([1], [IDS[:40]])
    pages, logits = a.export_kv(1)
    assert pages.shape[1:] == a.page_payload_spec[0] == (4, 16, 128)
    b.import_kv(7, IDS[:40], pages, logits)
    got = [int(t) for t in b.decode_pipeline([7]).run(6)[0]]
    a.put([2], [IDS[:40]])
    want = [int(t) for t in a.decode_pipeline([2]).run(6)[0]]
    assert got == want


def test_frontend_serves_the_family():
    """``ServingFrontend`` over the engine, the normal path: three requests,
    each stream the engine's own greedy stream for its prompt."""
    cfg, model, params = build("held")
    eng = engine_for(model, params)
    prompts = [IDS[:20], IDS[10:45], IDS[30:36]]
    with eng.serving_frontend() as fe:
        handles = [fe.submit(p.tolist(), max_new_tokens=8) for p in prompts]
        fe.drain()
    for p, h in zip(prompts, handles):
        seq = np.concatenate([p, np.asarray(h.tokens[:-1], np.int32)])
        ref = np.asarray(reference(cfg, params, seq))
        assert list(h.tokens) == list(np.argmax(ref[len(p) - 1:], axis=-1))
