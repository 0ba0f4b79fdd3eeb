"""The plain reference for ZAYA1 (``chipbench/reference/zaya_ref.py``)
against a few lines of numpy that run its equations a token at a time,
carrying the tail a cache would; the zoo's module against it; each part of
the mathematics shown to matter; the skip choice and the router's state; and
the cell the configuration runs in: its files, its arithmetic, its traffic,
its metrics' readers. What this file says of ``BENCHMARK.json`` it says by
membership, not by place: a later cell moves nothing here."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.harness import Registry  # noqa: E402
from chipbench.reference import zaya_ref as ref  # noqa: E402

CELL = "zaya1-8b-serve-pp2.reasoning-closed-64"
CONFIG = "zaya1-8b-serve-pp2"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
OWN = ("cca_share.reason64", "cca_mix_share.reason64",
       "moe_router_share.reason64")
SAME = lambda x: x


def family():
    return Registry().module("families", "zaya")


def tiny(seed=0, **kw):
    """Three layers at toy widths, every norm's gain moved off one, as
    (config, module, params, configuration-file keys)."""
    from deepspeed_tpu.models.zaya import ZayaConfig, ZayaForCausalLM
    kw = dict(dict(head_dim=16, hidden_size=64, moe_intermediate_size=32,
                   router_hidden_size=16), **kw)
    cfg = ZayaConfig.tiny(dtype=jnp.float32, **kw)
    model = ZayaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 1000))

    def shake(path, leaf):
        if any("norm" in getattr(p, "key", "") for p in path):
            return leaf + 0.2 * jax.random.normal(next(keys), leaf.shape)
        return leaf

    params = jax.tree_util.tree_map_with_path(shake, params)
    d = {k: getattr(cfg, k) for k in family().MODEL_KEYS}
    d["rope_parameters"] = {"hybrid": {"rope_theta": cfg.rope_theta}}
    return cfg, model, params, d


def close(got, want, tol=1e-4):
    got, want = np.asarray(got), np.asarray(want)
    return np.max(np.abs(got - want)) <= tol * np.max(np.abs(want))


@pytest.fixture(scope="module")
def model():
    return tiny()


def weights_hp(model, **hp_over):
    _, _, params, d = model
    fam = family()
    return fam.reference_weights(params, d), {**fam.reference_hp(d),
                                              **hp_over}


def logits_of(model, ids, **hp_over):
    weights, hp = weights_hp(model, **hp_over)
    return np.asarray(ref.forward_logits(weights, ids, hp))


# --------------------------------------------------------------------------- #
# compressed convolutional attention a token at a time
# --------------------------------------------------------------------------- #

def numpy_cca_stepwise(u, layer, hp):
    """q, k, v of every token from ``[s ; z]`` of the two tokens before it,
    kept as a serving system would keep them — the equations of the
    reference's docstring written for ONE token — then attention over
    everything cached so far."""
    f = lambda n: np.asarray(layer[n], np.float64)
    Hq, Hk, d = hp["num_heads"], hp["num_kv_heads"], hp["head_dim"]
    G, C, rd = Hq // Hk, (Hq + Hk) * d, hp["rotary_dim"]
    w0, w1 = f("conv0_w"), f("conv1_w").reshape(Hq + Hk, d, d, 2)
    s1 = s2 = np.zeros(C)       # s_{t-1}, s_{t-2}
    z1 = np.zeros(d)            # z_{t-1}
    freqs = hp["rope_theta"] ** (-np.arange(rd // 2) * 2.0 / rd)
    keys, values, outs = [], [], []
    for t, row in enumerate(np.asarray(u, np.float64)):
        qp, kp = row @ f("wq"), row @ f("wk")
        v1, z = row @ f("wv1"), row @ f("wv2")
        s = np.concatenate([qp, kp])
        m_now = w0[:, 0] * s1 + w0[:, 1] * s + f("conv0_b")
        m_old = w0[:, 0] * s2 + w0[:, 1] * s1 + f("conv0_b")
        y = f("conv1_b") + np.concatenate([
            w1[h, :, :, 0] @ m_old[h * d:(h + 1) * d]
            + w1[h, :, :, 1] @ m_now[h * d:(h + 1) * d]
            for h in range(Hq + Hk)])
        qh, kh = qp.reshape(Hk, G, d), kp.reshape(Hk, d)
        q = y[:Hq * d].reshape(Hk, G, d) + (qh + kh[:, None]) / 2
        k = y[Hq * d:].reshape(Hk, d) + (kh + qh.mean(axis=1)) / 2
        unit = lambda x: x / np.sqrt((x * x).mean(-1, keepdims=True)
                                     + hp["eps"])
        q, k = unit(q).reshape(Hq, d), unit(k) * f("temp")[:, None]

        def turn(x):
            c, sn = np.cos(t * freqs), np.sin(t * freqs)
            a, b = x[:, :rd // 2], x[:, rd // 2:rd]
            return np.concatenate([a * c - b * sn, b * c + a * sn,
                                   x[:, rd:]], axis=1)

        q, k = turn(q), turn(k)
        keys.append(k)
        values.append(np.stack([v1, z1]))
        K, V = np.stack(keys), np.stack(values)         # [t + 1, Hk, d]
        sc = np.einsum("gd,sgd->gs", q.reshape(Hk, G, d).reshape(-1, d),
                       np.repeat(K, G, axis=1)) * d ** -0.5
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        outs.append(np.einsum("gs,sgd->gd", p,
                              np.repeat(V, G, axis=1)).reshape(-1))
        s2, s1, z1 = s1, s, z
    # [s ; z] of the last two tokens (the older one's z is not read again)
    tail = np.stack([np.concatenate([s2, np.zeros(d)]),
                     np.concatenate([s1, z1])])
    return np.stack(outs) @ f("wo"), tail


def test_the_block_is_its_equations_a_token_at_a_time(model):
    weights, hp = weights_hp(model)
    layer = weights["layers"][1]
    u = np.random.default_rng(0).standard_normal((9, 64)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        out, tail = ref.cca(jnp.asarray(u), layer, hp, SAME, SAME)
    want, want_tail = numpy_cca_stepwise(u, layer, hp)
    assert close(out, want, 1e-4)
    # the newest token's [s ; z] is the tail's last column; the one before
    # it holds s of the token before (its z is not read again)
    C = want_tail.shape[1] - hp["head_dim"]
    assert close(np.asarray(tail).T[1], want_tail[1], 1e-5)
    assert close(np.asarray(tail).T[0, :C], want_tail[0, :C], 1e-5)


def test_the_input_is_padded_not_the_intermediate(model):
    """At position 0 the second convolution reads ``m_{-1} = b0``, not
    zero: two stacked Conv1d over a left-padded input."""
    weights, hp = weights_hp(model)
    layer = {k: np.asarray(v) for k, v in weights["layers"][0].items()}
    s = jnp.asarray(np.random.default_rng(1).standard_normal(
        (3, 6 * 16)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        y = np.asarray(ref.cca_mix(s, layer, hp, SAME, SAME))
    w0, b0 = layer["conv0_w"], layer["conv0_b"]
    w1 = layer["conv1_w"].reshape(6, 16, 16, 2)
    m0 = (w0[:, 1] * np.asarray(s)[0] + b0).reshape(6, 16)
    want = layer["conv1_b"].reshape(6, 16) \
        + np.einsum("hoi,hi->ho", w1[..., 0], b0.reshape(6, 16)) \
        + np.einsum("hoi,hi->ho", w1[..., 1], m0)
    assert close(y[0], want.reshape(-1), 1e-5)


# --------------------------------------------------------------------------- #
# the whole model
# --------------------------------------------------------------------------- #

def test_the_zoo_module_is_the_reference(model):
    """The published layout maps one to one: the flax module's dense forward
    and the reference agree on the same parameter tree."""
    _, module, params, _ = model
    ids = np.random.default_rng(0).integers(0, 256, 30).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(module.apply({"params": params}, ids[None]))[0]
    assert close(logits_of(model, ids), want, 2e-4)


@pytest.mark.parametrize("fault, least", [
    ({"drop": ("router_state",)}, 1e-2), ({"drop": ("value_shift",)}, 1e-2),
    ({"drop": ("qk_mean",)}, 1e-2), ({"drop": ("temp",)}, 1e-2),
    ({"drop": ("conv_bias",)}, 1e-2), ({"drop": ("conv_history",)}, 1e-2),
    ({"drop": ("skip",)}, 1e-2), ({"drop": ("res_bias",)}, 1e-2),
    # a router rounded to bfloat16 moves every row's weight p_e by its
    # rounding, and a row whose choice it changes by far more
    ({"router_dtype": "bfloat16"}, 1e-3)],
    ids=lambda f: "-".join(f.get("drop", ("bf16_router",)))
    if isinstance(f, dict) else "")
def test_a_part_left_out_moves_the_logits(fault, least):
    """What the serving tests' tolerance (2e-4) has to tell apart: every
    fault moves some row's logits by a hundredth of their largest and more
    (a thousandth, for a router in bfloat16 that changes no choice here)."""
    model = tiny(seed=3)
    ids = np.random.default_rng(1).integers(0, 256, 120).astype(np.int32)
    got, want = logits_of(model, ids, **fault), logits_of(model, ids)
    worst = np.max(np.abs(got - want), axis=-1) / np.max(np.abs(want),
                                                         axis=-1)
    assert worst.max() > least


def test_a_token_that_skips_keeps_the_scaled_stream(model):
    """Step 4: the branch of a token whose choice is the last is exactly
    zero, whatever the experts hold; pushed onto every token of a layer, the
    model is the model with that layer's experts zeroed."""
    weights, hp = weights_hp(model)
    layer = dict(weights["layers"][1])
    g = jnp.asarray(np.random.default_rng(2).standard_normal((200, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        out, _, _, skipped = ref.sparse_mixture(g, layer, hp, None)
    skipped = np.asarray(skipped)
    assert 0 < skipped.sum() < 200
    assert (np.asarray(out)[skipped] == 0).all()
    assert (np.abs(np.asarray(out)[~skipped]).max(axis=-1) > 0).all()
    ids = np.random.default_rng(3).integers(0, 256, 25).astype(np.int32)
    push = dict(layer, beta=np.asarray(layer["beta"]).copy())
    push["beta"][-1] = 9.0
    none = dict(layer, w_down=np.zeros_like(layer["w_down"]))
    run = lambda l1: np.asarray(ref.forward_logits(
        {**weights, "layers": [weights["layers"][0], l1,
                               weights["layers"][2]]}, ids, hp))
    assert np.array_equal(run(push), run(none))
    assert not close(run(push), run(layer), 1e-2)


def test_the_first_router_is_handed_no_state(model):
    """Step 1: a router whose ``gamma`` is zero is the first layer's; the
    state it returns is its own down-projection, and the next layer's
    choice depends on it."""
    weights, hp = weights_hp(model)
    layer = weights["layers"][1]
    rng = np.random.default_rng(4)
    g = jnp.asarray(rng.standard_normal((50, 64)), jnp.float32)
    r_in = jnp.asarray(rng.standard_normal((50, 16)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        first = ref.route(g, layer, hp, None)
        zeroed = ref.route(g, dict(layer, gamma=np.zeros(16, np.float32)),
                           hp, r_in)
        later = ref.route(g, layer, hp, r_in)
    for a, b in zip(first, zeroed):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert close(np.asarray(later[2]) - np.asarray(first[2]),
                 np.asarray(layer["gamma"]) * np.asarray(r_in), 1e-5)
    assert (np.asarray(later[0]) != np.asarray(first[0])).any()
    # top-1, not renormalised: one weight a token, under 1 (or none)
    dense = np.asarray(first[0])
    assert ((dense > 0).sum(axis=-1) <= 1).all() and dense.max() < 1


def test_rows_tails_and_variants(model):
    weights, hp = weights_hp(model)
    ids = np.random.default_rng(2).integers(0, 256, 20).astype(np.int32)
    whole = np.asarray(ref.forward_logits(weights, ids, hp))
    some, margin, tails = ref.forward_logits(
        weights, ids, hp, rows=[3, 19], with_margin=True, with_state=True)
    assert close(some, whole[[3, 19]], 1e-6) and margin.shape == (2,)
    assert (np.asarray(margin) >= 0).all()
    assert tails.shape == (3, 1, 6 * 16 + 16, 2)    # [L, 1, C + d, taps]
    skipped = []
    low, ctl = ref.forward_variants(weights, ids, hp, [
        {"act_dtype": jnp.bfloat16, "head": False},
        {"act_dtype": jnp.bfloat16, "head": False,
         "state_dtype": jnp.float8_e4m3fn}], skipped=skipped)
    assert low[0] is None and len(skipped) == 3
    err = lambda a, b: float(np.sqrt(np.mean((np.asarray(a) - np.asarray(b))
                                             ** 2) / np.mean(np.asarray(b)
                                                             ** 2)))
    # a tail kept at a lower precision is seen in the tail itself
    assert err(ctl[2][0], low[2][0]) > 1e-2
    # and the tail's channel order is the one asked for
    plain = ref.forward_logits(weights, ids, {**hp, "tail_order": None},
                               with_state=True)[1]
    assert np.array_equal(np.asarray(plain)[:, :, np.asarray(
        hp["tail_order"])], np.asarray(tails))


def test_the_familys_weights_come_with_balanced_routers():
    """``init_params``: the same weights from the same seed, and balancing
    biases that do what their name says — on fresh unit-normal rows every
    expert of every layer is chosen (no one of them by twice its share) and a
    few percent of the rows skip; the zoo's own draw leaves the skip choice
    at -0.05."""
    from deepspeed_tpu.inference.v2 import ragged_model as rm
    fam = family()
    cfg = dict(Registry().config(CONFIG), num_hidden_layers=3,
               vocab_size=512, hidden_size=256, moe_intermediate_size=128)
    model = fam.build_model(cfg, jnp.float32)
    params = fam.init_params(model, 7, jnp.float32)
    again = fam.init_params(model, 7, jnp.float32)
    same = jax.tree_util.tree_map(lambda a, b: bool((a == b).all()),
                                  params, again)
    assert all(jax.tree_util.tree_leaves(same))
    spec, w = rm.adapt_zaya(params, model.config)
    routers = {k: v for k, v in w["layers"]["moe"].items()
               if k.startswith("router_")}
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2048, 256)),
                    jnp.float32)
    r = jnp.zeros((2048, 256), jnp.float32)
    for l in range(3):
        wl = jax.tree_util.tree_map(lambda a: a[l], routers)
        _, ids, r = rm.moe_route_mlp(x, wl, spec.moe, r, spec.eps)
        load = np.bincount(np.asarray(ids[:, 0]), minlength=17) / 2048
        assert load[:16].min() > 0.02 and load[:16].max() < 2 / 16, load
        assert 0.005 < load[16] < 0.08, load
    drawn = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    beta = drawn["params"]["layers_1"]["mlp"]["balancing_bias"]
    assert float(beta[-1]) == pytest.approx(-0.05) \
        and float(jnp.abs(beta[:-1]).max()) < 0.06


# --------------------------------------------------------------------------- #
# the configuration, its cell and its metrics
# --------------------------------------------------------------------------- #

def test_the_registry_finds_the_cell_and_its_files():
    reg = Registry()
    cell = reg.cell(CELL)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert cell["driver"] == "serve_closed_state_moe"
    assert reg.config(CONFIG)["family"] == "zaya"
    names = {m["name"] for m in reg.metrics_of(CELL, "per_layer")}
    assert set(OWN) | {
        "decode_step_ms.serve", "host_ms_per_step.serve",
        "decode_rows_mean.serve", "compiles_in_window.serve",
        "device_idle_share.serve", "prefill_device_share.serve",
        "kv_flush_share.serve", "engine_unaccounted_share.serve",
        "kv_pages_peak_share.serve", "moe_ffn_share.serve",
        "attn_full_share.serve", "state_slots_peak_share.serve"} <= names
    assert not {"ssm_share.serve", "gdn_share.longdoc",
                "moe_shared_share.serve"} & names
    assert {m["name"] for m in reg.metrics_of(CELL, "end_to_end")} == {
        "serve_tok_s", "setup_s"}
    for name in names:
        spec = reg.layer_metric(name)
        assert callable(reg.reader(spec["reader"]))
    for name, scope in zip(OWN, ("cca", "cca/mix", "moe_ffn/router")):
        spec = reg.layer_metric(name)
        assert spec["reader"] == "named.scope_share"
        assert spec["args"] == {"scope": scope}
    assert set(reg.cell(CELL)["layer_notes"]) <= names


def test_the_cell_is_an_entry_of_its_own_on_one_chip():
    cells = [w for w in BENCH["workloads"] if w["config"] == CONFIG]
    assert [w["name"] for w in cells] == [CELL] and cells[0]["chips"] == 1
    (config,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == ["num_hidden_layers"]
    own = [m for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in own) == sorted(OWN)
    assert {(m["layer"], m["moves"], m["unit"], m["source"]) for m in own} \
        == {("serving programs", "serve_tok_s", "%", "device_trace")}
    (tok_s,) = [m for m in BENCH["end_to_end"] if m["name"] == "serve_tok_s"]
    assert CELL in tok_s["workloads"]


def test_the_scope_patterns_tell_the_new_scopes_apart():
    from chipbench.reduce import hlo_names
    name = "jit(serve_decode_step)/while/body/attn/cca/{}/dot_general"
    hit = lambda scope, op: bool(hlo_names.scope_pattern(scope).search(op))
    assert hit("cca", name.format("mix")) and hit("cca/mix",
                                                  name.format("mix"))
    assert hit("cca", name.format("attn_full")) \
        and hit("attn_full", name.format("attn_full"))
    assert not hit("cca/mix", name.format("proj"))
    router = "jit(serve_decode_step)/while/body/ffn/moe_ffn/router/mlp/erf"
    assert hit("moe_ffn/router", router) and hit("moe_ffn", router)
    assert not hit("moe_ffn/router",
                   "jit(x)/while/body/ffn/moe_ffn/experts/pallas_call")


def test_the_traffic_is_the_issues():
    mix = Registry().traffic("reasoning-closed-64")
    assert (mix["kind"], mix["clients"], mix["pool_requests"], mix["ramp_s"],
            mix["drain_s"], mix["sampling"]) == (
                "serve_closed", 64, 512, 15.0, 120.0, "greedy")
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 768,
                                    "sigma": 1.0, "min": 64, "max": 8192}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 512,
                                    "max": 3072}
    warm = mix["warmup"]
    assert warm["requests"] == 64
    assert warm["prompt_tokens"] == mix["prompt_tokens"]
    assert warm["output_tokens"] == {"dist": "uniform", "min": 8, "max": 40}
    # reasoning-closed.json's length distributions exactly, at half its clients
    full = Registry().traffic("reasoning-closed")
    for key in ("prompt_tokens", "output_tokens", "ramp_s", "sampling"):
        assert mix[key] == full[key]
    assert (full["clients"], full["pool_requests"]) == (128, 1024)


def test_the_file_holds_the_published_widths():
    cfg = Registry().config(CONFIG)
    published = {
        "attention_bias": False, "cca_time0": 2, "cca_time1": 2,
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "lm_head_bias": False, "max_position_embeddings": 131072,
        "model_type": "zaya", "moe_intermediate_size": 2048,
        "num_attention_heads": 8, "num_experts": 16,
        "num_experts_per_tok": 1, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
        "router_hidden_size": 256, "sliding_window": None,
        "tie_word_embeddings": True, "vocab_size": 262272,
        "layer_types": ["hybrid"] * 40,
        "rope_parameters": {
            "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                       "rope_type": "default"},
            "hybrid_sliding": {"partial_rotary_factor": 0.5,
                               "rope_theta": 10000, "rope_type": "default"},
            "rope_type": "default"}}
    assert {k: cfg[k] for k in published} == published
    assert cfg["num_hidden_layers"] == 20 and cfg["reduced"] == [
        "num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 40}
    d = cfg["deployment"]
    assert (d["pipeline_stages"], d["stage"]) == (2, 0)
    assert set(cfg["assumed"]) >= {
        "residual_scaling", "convolutions", "qk_mean", "value_shift",
        "norm_and_temperature", "rotation", "router", "skip_choice"}
    fam = family()
    assert fam.kv_layout(cfg) == (20, 2, 128)
    assert fam.rope_theta(cfg) == 5e6
    model = fam.build_model(cfg, jnp.bfloat16)
    assert model.config.num_hidden_layers == 20 \
        and model.config.layer_types == ("hybrid",) * 20
    assert model.config.conv_dim == 1280 and model.config.tail_taps == 2


def test_the_memory_account_is_its_arithmetic():
    """The file's numbers recomputed from its widths; the engine is held to
    the slot's and the page's bytes on the chip (``check_engine``)."""
    cfg = Registry().config(CONFIG)
    n = cfg["memory_account_numbers"]
    H, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    Hq, Hk, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    E, F, R = (cfg["num_experts"], cfg["moe_intermediate_size"],
               cfg["router_hidden_size"])
    C = (Hq + Hk) * d
    attn = H * (Hq * d + Hk * d + 2 * d) + Hq * d * H
    convs = C * cfg["cca_time0"] + C + C * d * cfg["cca_time1"] + C + Hk
    router = H * R + R + R + R + 2 * (R * R + R) + R * (E + 1) + (E + 1)
    layer = E * 3 * H * F + attn + convs + router + 2 * H + 8 * H
    params = L * layer + V * H + H
    assert n["weight_bytes"] == 2 * params == 9377620728
    state = family().state_layout(cfg)
    assert (state["conv_dim"], state["tail_channels"], state["taps"],
            state["conv_width"]) == (1280, 1408, 2, 2048)
    assert n["state_bytes_a_sequence"] == state["bytes_per_sequence"] \
        == L * 4 * 2 * 2048
    sm = cfg["engine"]["state_manager"]
    assert n["state_slots"] == sm["max_tracked_sequences"] + 1
    assert n["state_pool_bytes"] == n["state_slots"] \
        * n["state_bytes_a_sequence"]
    assert n["bytes_a_page"] == cfg["engine"]["kv_cache"]["block_size"] \
        * L * 2 * Hk * d * 2
    budget = int(n["hbm_limit_bytes"] * cfg["hbm_fill"]) - n["weight_bytes"] \
        - n["state_pool_bytes"] - cfg["hbm_headroom_bytes"]
    assert n["page_budget_bytes"] == budget
    assert n["pages"] == budget // n["bytes_a_page"] == 1810
    assert n["tokens"] == n["pages"] * 128
    assert sm["max_ragged_batch_size"] == sm["max_ragged_sequence_count"] \
        + 4 * sm["prefill_chunk_size"]
    # rows bind: 64 clients' mean request is funded well inside the pages
    assert 64 * (1270 + 1792) < n["tokens"]
    # the longest request and one decode slice fit the context
    assert 8192 + 3072 + 9 <= sm["max_context"]
