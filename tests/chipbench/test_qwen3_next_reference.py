"""The plain reference for Qwen3-Next (``chipbench/reference/
qwen3_next_ref.py``) against a few lines of numpy written from the same
equations; the published fused layouts against hand-built ones; the shares of
the experts against the uncut layer; the zoo's module against it; each part
of the mathematics shown to matter; and the cell the configuration runs in:
its files, its arithmetic, its traffic, its metrics' readers."""

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
from chipbench.reduce import gdn_work  # noqa: E402
from chipbench.reference import qwen3_next_ref as ref  # noqa: E402

CELL = "qwen3-next-serve-ep8.longdoc-closed"
CONFIG = "qwen3-next-serve-ep8"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
HP = {"key_heads": 2, "value_heads": 4, "key_dim": 8, "value_dim": 6,
      "eps": 1e-6}


def family():
    return Registry().module("families", "qwen3_next")


def tiny(seed=0, **kw):
    """A two-period model at toy widths, every norm's weight moved off its
    initial value, as (config, params, configuration-file keys)."""
    from deepspeed_tpu.models.qwen3_next import (Qwen3NextConfig,
                                                 Qwen3NextForCausalLM)
    kw = dict(dict(linear_key_head_dim=16, linear_value_head_dim=8,
                   linear_num_key_heads=2, linear_num_value_heads=4,
                   num_hidden_layers=4), **kw)
    cfg = Qwen3NextConfig.tiny(dtype=jnp.float32, **kw)
    model = Qwen3NextForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 1000))

    def shake(path, leaf):
        if any("norm" in getattr(p, "key", "") for p in path):
            return leaf + 0.2 * jax.random.normal(next(keys), leaf.shape)
        return leaf

    params = jax.tree_util.tree_map_with_path(shake, params)
    fam = family()
    d = {k: getattr(cfg, k) for k in fam.MODEL_KEYS}
    first, count = cfg.held
    d.update(num_experts=count, deployment={"held_first": first},
             published={"num_experts": cfg.num_experts})
    return cfg, model, params, d


def close(got, want, tol=1e-4):
    got, want = np.asarray(got), np.asarray(want)
    return np.max(np.abs(got - want)) <= tol * np.max(np.abs(want))


# --------------------------------------------------------------------------- #
# the recurrence and the mixer against numpy
# --------------------------------------------------------------------------- #

def numpy_delta_rule(q, k, v, g, beta):
    """S' = e^g S; S = S' + beta k (v - S'^T k)^T; o = S^T q, a head."""
    T, H, N = k.shape
    S = np.zeros((H, N, v.shape[-1]))
    out = np.zeros(v.shape)
    for t in range(T):
        for h in range(H):
            Sd = np.exp(g[t, h]) * S[h]
            S[h] = Sd + beta[t, h] * np.outer(k[t, h], v[t, h] - Sd.T @ k[t, h])
            out[t, h] = S[h].T @ q[t, h]
    return out, S


def test_recurrence_is_the_delta_rule():
    rng = np.random.default_rng(0)
    T, H, N, P = 40, 3, 8, 6
    q, k = rng.standard_normal((2, T, H, N))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((T, H, P))
    g = np.log(rng.uniform(0.2, 1.0, (T, H)))
    beta = rng.uniform(0, 1, (T, H))
    want, S = numpy_delta_rule(q, k, v, g, beta)
    with jax.default_matmul_precision("highest"):
        o, Sj = ref.recurrence(*(jnp.asarray(x, jnp.float32)
                                 for x in (q, k, v, g, beta)))
    assert close(o, want, 1e-5) and close(Sj, S, 1e-5)


def test_a_state_that_only_adds_is_another_model():
    """Without the correction (``beta k v^T`` added, as the state pool's other
    tenants do) the same inputs give another output."""
    rng = np.random.default_rng(1)
    T, H, N, P = 30, 2, 8, 6
    q, k = rng.standard_normal((2, T, H, N))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((T, H, P))
    g = np.log(rng.uniform(0.8, 1.0, (T, H)))
    beta = rng.uniform(0.5, 1, (T, H))
    args = [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta)]
    o, _ = ref.recurrence(*args)
    added, _ = ref.recurrence(*args, drop=("delta",))
    assert not close(added, o, 0.05)


def mixer_weights(rng, hidden=12, K=4):
    Hk, Hv, N, P = (HP[k] for k in ("key_heads", "value_heads", "key_dim",
                                    "value_dim"))
    f = lambda *s: rng.standard_normal(s).astype(np.float32)
    return {"w_qkvz": f(hidden, 2 * Hk * N + 2 * Hv * P) / 3,
            "w_ba": f(hidden, 2 * Hv), "conv_w": f(2 * Hk * N + Hv * P, K),
            "b_dt": f(Hv), "A_log": np.log(rng.uniform(0.01, 2, Hv)
                                           ).astype(np.float32),
            "g_norm": 1 + 0.3 * f(P), "w_out": f(Hv * P, hidden)}


def numpy_mixer(u, w):
    """The Gated DeltaNet block of the module's docstring in numpy, with its
    own reading of the published layouts."""
    Hk, Hv, N, P = (HP[k] for k in ("key_heads", "value_heads", "key_dim",
                                    "value_dim"))
    R, T, K = Hv // Hk, u.shape[0], w["conv_w"].shape[1]
    x = (u @ w["w_qkvz"]).reshape(T, Hk, 2 * N + 2 * R * P)
    y = (u @ w["w_ba"]).reshape(T, Hk, 2 * R)
    q, k = x[..., :N].reshape(T, -1), x[..., N:2 * N].reshape(T, -1)
    v = x[..., 2 * N:2 * N + R * P].reshape(T, -1)
    z = x[..., 2 * N + R * P:].reshape(T, Hv, P)
    b, a = y[..., :R].reshape(T, Hv), y[..., R:].reshape(T, Hv)
    mixed = np.concatenate([q, k, v], axis=1)
    pad = np.concatenate([np.zeros((K - 1, mixed.shape[1])), mixed])
    c = sum(pad[j:j + T] * w["conv_w"][:, j] for j in range(K))
    c = c / (1 + np.exp(-c))
    unit = lambda m: m / np.sqrt((m * m).sum(-1, keepdims=True) + 1e-6)
    qh = np.repeat(unit(c[:, :Hk * N].reshape(T, Hk, N)), R, 1) * N ** -0.5
    kh = np.repeat(unit(c[:, Hk * N:2 * Hk * N].reshape(T, Hk, N)), R, 1)
    vh = c[:, 2 * Hk * N:].reshape(T, Hv, P)
    g = -np.exp(w["A_log"]) * np.log1p(np.exp(a + w["b_dt"]))
    o, _ = numpy_delta_rule(qh, kh, vh, g, 1 / (1 + np.exp(-b)))
    n = o / np.sqrt((o * o).mean(-1, keepdims=True) + HP["eps"]) * w["g_norm"]
    return (n * (z / (1 + np.exp(-z)))).reshape(T, -1) @ w["w_out"]


def test_delta_mixer_is_the_published_block():
    rng = np.random.default_rng(2)
    w = mixer_weights(rng)
    u = rng.standard_normal((25, 12)).astype(np.float32)
    none = {"c": jnp.asarray(False)}
    with jax.default_matmul_precision("highest"):
        out, S = ref.delta_mixer(jnp.asarray(u), w, HP, lambda x: x, none,
                                 None)
    assert close(out, numpy_mixer(u.astype(np.float64), w), 1e-4)
    assert S.shape == (4, 6, 8)            # [Hv, P, N]: the driver's layout


@pytest.mark.parametrize("fault", [
    {"drop": ("conv_history",)}, {"drop": ("decay",)}, {"drop": ("delta",)},
    {"drop": ("gate",)}, {"gate_before_norm": True}])
def test_each_part_of_the_mixer_shows(fault):
    rng = np.random.default_rng(3)
    w = mixer_weights(rng)
    u = jnp.asarray(rng.standard_normal((25, 12)), jnp.float32)
    none = {"c": jnp.asarray(False)}
    run = lambda hp: ref.delta_mixer(u, w, hp, lambda x: x, none, None)[0]
    assert not close(run({**HP, **fault}), run(HP), 1e-2)


# --------------------------------------------------------------------------- #
# the published fused layouts
# --------------------------------------------------------------------------- #

def test_qkvz_and_ba_are_split_a_key_head_at_a_time():
    """Columns that say what they are: 1000 * kind + 100 * head + index."""
    Hk, Hv, N, P = 2, 4, 3, 5
    hp = {"key_heads": Hk, "value_heads": Hv, "key_dim": N, "value_dim": P}
    cols, ba = [], []
    for hk in range(Hk):
        cols += [1000 + 100 * hk + i for i in range(N)]          # q
        cols += [2000 + 100 * hk + i for i in range(N)]          # k
        for kind in (3000, 4000):                                # v, then z
            for r in range(Hv // Hk):
                cols += [kind + 100 * (2 * hk + r) + i for i in range(P)]
        ba += [5000 + 2 * hk + r for r in range(2)]              # b
        ba += [6000 + 2 * hk + r for r in range(2)]              # a
    q, k, v, z, b, a = ref.split_qkvz(jnp.asarray([cols], jnp.float32),
                                      jnp.asarray([ba], jnp.float32), hp)
    for kind, got, heads, width in ((1000, q, Hk, N), (2000, k, Hk, N),
                                    (3000, v, Hv, P), (4000, z, Hv, P)):
        want = [[kind + 100 * h + i for i in range(width)]
                for h in range(heads)]
        assert (np.asarray(got[0]) == np.asarray(want)).all(), kind
    assert np.asarray(b[0]).tolist() == [5000, 5001, 5002, 5003]
    assert np.asarray(a[0]).tolist() == [6000, 6001, 6002, 6003]


def test_q_proj_holds_a_heads_query_then_its_gate():
    H, D = 3, 4
    cols = []
    for h in range(H):
        cols += [100 * h + i for i in range(D)]
        cols += [1000 + 100 * h + i for i in range(D)]
    q, gate = ref.split_q_gate(jnp.asarray([cols], jnp.float32), H, D)
    assert (np.asarray(q[0]) == [[100 * h + i for i in range(D)]
                                 for h in range(H)]).all()
    assert np.asarray(gate[0]).tolist() == [
        1000 + 100 * h + i for h in range(H) for i in range(D)]


def test_rotation_pairs_value_i_with_i_plus_half():
    """One head of 8 with 4 rotated: (0, 2) and (1, 3) turn together, the
    last four pass; the interleaved pairing is another model."""
    x = jnp.arange(1.0, 9.0).reshape(1, 1, 8)
    at = jnp.asarray([3])
    got = np.asarray(ref.rope(x, at, 100.0, 4))[0, 0]
    ang = 3 * 100.0 ** (-np.arange(0, 4, 2) / 4)
    want = [1 * np.cos(ang[0]) - 3 * np.sin(ang[0]),
            2 * np.cos(ang[1]) - 4 * np.sin(ang[1]),
            3 * np.cos(ang[0]) + 1 * np.sin(ang[0]),
            4 * np.cos(ang[1]) + 2 * np.sin(ang[1]), 5, 6, 7, 8]
    assert np.allclose(got, want, atol=1e-5)
    other = np.asarray(ref.rope(x, at, 100.0, 4, interleaved=True))[0, 0]
    assert not np.allclose(other, want, atol=1e-2)


# --------------------------------------------------------------------------- #
# the shares of the experts
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("shares", [2, 4])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """8 experts over ``shares`` chips: the routed parts of all shares, plus
    the gated shared expert counted once, are the uncut layer."""
    rng = np.random.default_rng(shares)
    H, F, E = 16, 8, 8
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    layer = {"router": f(H, E), "w_gate": f(E, H, F), "w_up": f(E, H, F),
             "w_down": f(E, F, H), "shared_gate": f(H, 1),
             "shared": {"w_gate": f(H, F), "w_up": f(H, F), "w_down": f(F, H)}}
    u = f(20, H)
    hp = {"top_k": 3}
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.sparse_mixture(u, layer, hp)
        none = {**layer, **{k: layer[k][:0]
                            for k in ("w_gate", "w_up", "w_down")}}
        shared, _ = ref.sparse_mixture(u, none, {**hp, "held": (0, 0)})
        parts = 0.0
        for s in range(shares):
            first, count = s * E // shares, E // shares
            cut = {**layer, **{k: layer[k][first:first + count]
                               for k in ("w_gate", "w_up", "w_down")}}
            part, _ = ref.sparse_mixture(
                u, cut, {**hp, "held": (first, count), "drop": ("shared",)})
            parts = parts + part
    assert close(parts + shared, whole, 1e-5)
    # and a share renormalised over its own choices would not add up
    wrong, _ = ref.sparse_mixture(
        u, {**layer, **{k: layer[k][:4] for k in ("w_gate", "w_up",
                                                   "w_down")}},
        {**hp, "held": (0, 4), "drop": ("shared",), "norm_over_held": True})
    right, _ = ref.sparse_mixture(
        u, {**layer, **{k: layer[k][:4] for k in ("w_gate", "w_up",
                                                   "w_down")}},
        {**hp, "held": (0, 4), "drop": ("shared",)})
    assert not close(wrong, right, 1e-2)


def test_routing_is_softmax_top_k_renormalised():
    logits = jnp.asarray([[2.0, 0.0, 1.0, -1.0, 0.5]])
    layer = {"router": jnp.eye(5, dtype=jnp.float32)}
    dense, margin, held = ref.route(logits, layer, {"top_k": 2,
                                                    "held": (2, 2)})
    p = np.exp([2.0, 1.0]) / np.exp([2.0, 1.0]).sum()
    assert np.allclose(np.asarray(dense[0]), [p[0], 0, p[1], 0, 0], atol=1e-6)
    # expert 2 is held and chosen, 0.5 above the first one left out
    assert abs(float(margin[0]) - 0.5) < 1e-6
    assert np.asarray(held).tolist() == [False, False, True, True, False]


# --------------------------------------------------------------------------- #
# the whole model
# --------------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def model():
    return tiny()


def logits_of(model, ids, **hp_over):
    cfg, _, params, d = model
    fam = family()
    hp = {**fam.reference_hp(d), **hp_over}
    return np.asarray(ref.forward_logits(fam.reference_weights(params, d),
                                         ids, hp))


def test_the_zoo_module_is_the_reference(model):
    """The published layout maps one to one: the flax module's dense forward
    and the reference agree on the same parameter tree."""
    cfg, module, params, _ = model
    ids = np.random.default_rng(0).integers(0, 256, 30).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(module.apply({"params": params}, ids[None]))[0]
    assert close(logits_of(model, ids), want, 2e-4)


@pytest.mark.parametrize("fault", [
    {"drop": ("conv_history",)}, {"drop": ("decay",)}, {"drop": ("delta",)},
    {"drop": ("gate",)}, {"drop": ("attn_gate",)}, {"drop": ("shared",)},
    {"drop": ("shared_gate",)}, {"drop": ("rope",)}, {"drop": ("qk_norm",)},
    {"gate_before_norm": True}, {"plain_norm": True},
    {"interleaved_rope": True}])
def test_a_part_left_out_moves_the_logits(model, fault):
    """What the serving tests' tolerance (5e-4) has to tell apart: every
    fault moves the logits by a hundredth of their largest and more."""
    ids = np.random.default_rng(1).integers(0, 256, 30).astype(np.int32)
    assert not close(logits_of(model, ids, **fault), logits_of(model, ids),
                     1e-2)


def test_rows_states_and_variants(model):
    cfg, _, params, d = model
    fam = family()
    ids = np.random.default_rng(2).integers(0, 256, 20).astype(np.int32)
    weights, hp = fam.reference_weights(params, d), fam.reference_hp(d)
    whole = np.asarray(ref.forward_logits(weights, ids, hp))
    some, margin, states = ref.forward_logits(
        weights, ids, hp, rows=[3, 19], with_margin=True, with_state=True)
    assert close(some, whole[[3, 19]], 1e-6) and margin.shape == (2,)
    assert states.shape == (3, 4, 8, 16)       # [Ld, Hv, P, N]
    low, ctl = ref.forward_variants(weights, ids, hp, [
        {"act_dtype": jnp.bfloat16, "head": False},
        {"act_dtype": jnp.bfloat16, "head": False,
         "state_dtype": jnp.bfloat16}])
    assert low[0] is None
    err = lambda a, b: float(np.sqrt(np.mean((np.asarray(a) - np.asarray(b))
                                             ** 2) / np.mean(np.asarray(b)
                                                             ** 2)))
    # a state rounded after every token is seen in the state itself
    assert err(ctl[2][0], low[2][0]) > 1e-3


def test_slow_heads_keep_their_state(model):
    """One value head in four draws a small decay rate: exp(g) of the slow
    heads stays near 1 whatever the token, the others' far under it."""
    cfg, _, params, _ = model
    A = np.exp(np.asarray(params["layers_0"]["linear_attn"]["A_log"]))
    slow = np.arange(A.shape[0]) % cfg.slow_heads == 0
    g_slow = -A[slow] * np.log1p(np.exp(1.0))
    assert (np.exp(g_slow) > 0.97).all() and (A[~slow] > 1e-4).all()


# --------------------------------------------------------------------------- #
# the configuration, its cell and its metrics
# --------------------------------------------------------------------------- #

def test_the_registry_finds_the_cell_and_its_files():
    reg = Registry()
    cell = reg.cell(CELL)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert cell["driver"] == "serve_closed_state_moe"
    assert reg.config(CONFIG)["family"] == "qwen3_next"
    names = {m["name"] for m in reg.metrics_of(CELL, "per_layer")}
    assert {"gdn_share.longdoc", "gdn_step_share.longdoc",
            "gdn_scan_share.longdoc", "gdn_step_roofline_share.longdoc",
            "gdn_scan_roofline_share.longdoc", "state_slots_peak_share.serve",
            "attn_full_share.serve", "moe_ffn_share.serve"} <= names
    assert "ssm_share.serve" not in names
    assert {m["name"] for m in reg.metrics_of(CELL, "end_to_end")} == {
        "serve_tok_s", "setup_s"}
    for name in names:
        spec = reg.layer_metric(name)
        assert callable(reg.reader(spec["reader"]))


def test_the_cell_is_the_newest_entry_and_one_chip():
    assert BENCH["workloads"][-1]["name"] == CELL
    assert BENCH["configs"][-1]["name"] == CONFIG
    assert BENCH["configs"][-1]["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    own = [m for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in own] == [m["name"]
                                        for m in BENCH["per_layer"][-5:]]
    assert {m["layer"] for m in own} == {"delta-rule kernels"}


def test_the_traffic_is_the_issues():
    mix = Registry().traffic("longdoc-closed")
    assert (mix["kind"], mix["clients"], mix["pool_requests"], mix["ramp_s"],
            mix["drain_s"], mix["sampling"]) == (
                "serve_closed", 64, 512, 15.0, 120.0, "greedy")
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 4096,
                                    "sigma": 1.0, "min": 512, "max": 32768}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 256,
                                    "max": 1024}
    warm = mix["warmup"]
    assert warm["requests"] == 64
    assert warm["prompt_tokens"] == mix["prompt_tokens"]
    assert warm["output_tokens"] == {"dist": "uniform", "min": 8, "max": 40}


def test_the_file_holds_the_published_widths():
    cfg = Registry().config(CONFIG)
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts_per_tok": 10, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
        "use_sliding_window": False}
    assert {k: cfg[k] for k in published} == published
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (12, 64, 18992)
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                "vocab_size": 151936}
    d = cfg["deployment"]
    assert (d["expert_parallel"], d["vocabulary_split"],
            d["pipeline_stages"], d["ep_rank"], d["held_first"]) == (
                8, 8, 4, 0, 0)
    fam = family()
    assert fam.experts(cfg) == (512, (0, 64))
    assert fam.layer_kinds(cfg) == (["delta"] * 3 + ["attention"]) * 3
    assert fam.kv_layout(cfg) == (3, 2, 256)


def test_the_memory_account_is_its_arithmetic():
    """The file's numbers recomputed from its widths; the engine is held to
    the slot's and the page's bytes on the chip (``check_engine``)."""
    cfg = Registry().config(CONFIG)
    n = cfg["memory_account_numbers"]
    H, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    N, P = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    K, F = cfg["linear_conv_kernel_dim"], cfg["moe_intermediate_size"]
    conv = 2 * Hk * N + Hv * P
    delta = H * (2 * Hk * N + 2 * Hv * P) + H * 2 * Hv + conv * K \
        + 2 * Hv + P + Hv * P * H
    Hq, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    attn = H * Hq * 2 * D + 2 * H * Hkv * D + Hq * D * H + 2 * D
    moe = H * 512 + 3 * H * cfg["shared_expert_intermediate_size"] + H \
        + cfg["num_experts"] * 3 * H * F
    params = 9 * delta + 3 * attn + L * (moe + 2 * H) + 2 * V * H + H
    assert n["weight_bytes"] == 2 * params == 5858748800
    state = family().state_layout(cfg)
    assert state["conv_width"] == state["conv_dim"] == 8192
    assert n["state_bytes_a_sequence"] == state["bytes_per_sequence"] \
        == 9 * 4 * (N * Hv * P + (K - 1) * conv)
    sm = cfg["engine"]["state_manager"]
    assert n["state_slots"] == sm["max_tracked_sequences"] + 1
    assert n["state_pool_bytes"] == n["state_slots"] \
        * n["state_bytes_a_sequence"]
    assert n["bytes_a_page"] == cfg["engine"]["kv_cache"]["block_size"] \
        * 3 * 2 * Hkv * D * 2
    budget = int(n["hbm_limit_bytes"] * cfg["hbm_fill"]) - n["weight_bytes"] \
        - n["state_pool_bytes"] - cfg["hbm_headroom_bytes"]
    assert n["page_budget_bytes"] == budget
    assert n["pages"] == budget // n["bytes_a_page"]
    assert n["tokens"] == n["pages"] * 128
    assert sm["max_ragged_batch_size"] == sm["max_ragged_sequence_count"] \
        + 8 * sm["prefill_chunk_size"]


def test_the_kernels_work_is_counted_from_the_widths():
    w = gdn_work.widths(Registry().config(CONFIG))
    flops, bytes_ = gdn_work.decode_call(
        64, w["key_heads"], w["value_heads"], w["d_key"], w["d_value"],
        w["d_conv"])
    # 64 rows x (2 x 2 MiB of state + 2 x 96 KiB of tail + the operands)
    assert 64 * 2 * (2 << 20) < bytes_ < 64 * 2.2 * (2 << 20)
    assert flops / bytes_ < 1.0                      # bytes bound it
    per_token = gdn_work.scan_token_flops(16, 32, 128, 128, 64)
    assert per_token == 16 * 2 * 64 * 128 + 32 * (6 * 128 * 128 + 2 * 64 * 128)
    flops, bytes_ = gdn_work.scan_call(2048, 8, 16, 32, 128, 128, 64)
    assert flops == 2048 * per_token
    assert bytes_ > 8 * 2 * (2 << 20)


def test_the_roofline_readers_say_nothing_without_a_capture():
    """On a program that has neither the spans' ``live`` rows nor the pass
    counters (the parent), or in a run without a capture, the readers return
    nothing and do not raise."""
    readers = Registry().module("readers", "gdn")
    view = {"config": Registry().config(CONFIG), "peaks": {}, "trace": None}
    assert readers.step_roofline_share(view) is None
    assert readers.scan_roofline_share(view) is None
    other = {"config": {"hidden_size": 1}, "capture": object(),
             "op_names": {"x": {}}}
    assert readers.step_roofline_share(other) is None
    assert readers.scan_roofline_share(other) is None


def test_the_tools_table_cuts_a_program_by_scope():
    """``tools/gdn_roofline.py::program_tables`` on the recorded tiny trace:
    every execution counted, every operation's time under a scope or under
    ``other``."""
    from chipbench.harness import ANNOTATIONS
    from chipbench.reduce import hlo_names, xplane
    path = os.path.join(ROOT, "tests", "chipbench", "data",
                        "tiny_trace_scoped.xplane.pb")
    view = {"trace": xplane.load(path, ANNOTATIONS),
            "op_names": hlo_names.load(path)}
    tables = Registry().module("tools", "gdn_roofline").program_tables(view)
    (prog, table), = tables.items()
    assert prog.startswith("jit_") and table["runs"] == 3
    scopes = {k: v for k, v in table.items() if k not in ("runs", "ms")}
    assert abs(sum(scopes.values()) - table["ms"]) < 1e-3 and table["ms"] > 0
