"""The plain reference of the nemotron_h family
(``chipbench/reference/nemotron_h_ref.py``), its configuration file's
arithmetic, the family module, the cell's entries and the work functions of
the experts' grouped products and of the SSD kernels with groups."""

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
from chipbench.reference import nemotron_h_ref  # noqa: E402

CONFIG = os.path.join(ROOT, "chipbench", "configs",
                      "nemotron3-nano-serve-ep2.json")
CELL = "nemotron3-nano-serve-ep2" + ".reasoning-closed"


def family():
    return Registry().module("families", "nemotron_h")


def tiny(**kw):
    from deepspeed_tpu.models.nemotron_h import (NemotronHConfig,
                                                 NemotronHForCausalLM)
    cfg = NemotronHConfig.tiny(dtype=jnp.float32, **kw)
    model = NemotronHForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(3),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, model, params


def as_file(cfg):
    fam = family()
    d = {k: getattr(cfg, k) for k in fam.MODEL_KEYS}
    first, count = cfg.held
    d.update(n_routed_experts=count, deployment={"held_first": first},
             published={"n_routed_experts": cfg.n_routed_experts})
    return d


def weights_hp(cfg, params):
    fam, d = family(), as_file(cfg)
    return fam.reference_weights(params, d), fam.reference_hp(d)


@pytest.fixture(scope="module")
def built():
    cfg, model, params = tiny()
    ids = np.random.default_rng(0).integers(0, 256, 24).astype(np.int32)
    weights, hp = weights_hp(cfg, params)
    return cfg, model, params, ids, weights, hp


def close(a, b, tol):
    a, b = np.asarray(a), np.asarray(b)
    return np.max(np.abs(a - b)) <= tol * np.max(np.abs(b))


def test_the_recurrence_is_the_product_form_written_out():
    """A second, independent formulation: over the whole sequence as ONE
    chunk, ``y_t = sum_{s<=t} exp(cum_t - cum_s) (C_t . B_s) dt_s x_s`` and
    ``S = sum_s exp(cum_T - cum_s) dt_s x_s (outer) B_s``, in float64 numpy,
    a head and its group at a time — no scan, no state carried."""
    rng = np.random.default_rng(1)
    T, H, P, N, G = 20, 4, 8, 16, 2
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (T, H)))
    x = rng.standard_normal((T, H, P))
    B, C = rng.standard_normal((2, T, G, N))
    a = -rng.uniform(1.0, 16.0, H)
    y, S = nemotron_h_ref.recurrence(*(jnp.asarray(v, jnp.float32)
                                       for v in (dt, x, B, C, a)))
    for h in range(H):
        g = h // (H // G)
        cum = np.cumsum(dt[:, h] * a[h])
        decay = np.tril(np.exp(cum[:, None] - cum[None, :]))
        scores = decay * (C[:, g] @ B[:, g].T)
        want_y = scores @ (dt[:, h, None] * x[:, h])
        want_S = np.einsum("s,sp,sn->pn", np.exp(cum[-1] - cum) * dt[:, h],
                           x[:, h], B[:, g])
        assert close(y[:, h], want_y, 2e-5) and close(S[h], want_S, 2e-5)
    # a head reading the other group's B and C is another model
    y2, _ = nemotron_h_ref.recurrence(*(jnp.asarray(v, jnp.float32) for v in (
        dt, x, B[:, ::-1], C[:, ::-1], a)))
    assert not close(y2, y, 1e-2)


def test_two_tokens_of_an_expert_layer_by_hand():
    """The ``E`` block on two tokens, written out with numpy: sigmoid scores,
    the choice by score + bias, weights by score over the chosen ones' sum
    times the scale, two matrices with relu^2 between them, the shared
    expert unweighted, one norm, one residual."""
    cfg, _, params = tiny(num_hidden_layers=1, hybrid_override_pattern="E")
    weights, hp = weights_hp(cfg, params)
    ids = np.asarray([5, 9], np.int32)
    got = np.asarray(nemotron_h_ref.forward_logits(weights, ids, hp))
    f = lambda v: np.asarray(v, np.float64)
    L = weights["layers"][0]
    norm = lambda v, w: v / np.sqrt((v * v).mean(-1, keepdims=True)
                                    + cfg.norm_eps) * f(w)
    x = f(weights["embed"])[ids]
    u = norm(x, L["ln"])
    s = 1.0 / (1.0 + np.exp(-(u @ f(L["router"]))))
    out = np.zeros_like(u)
    for t in range(2):
        chosen = np.argsort(-(s[t] + f(L["bias"])))[:cfg.num_experts_per_tok]
        total = s[t, chosen].sum()
        for e in chosen:
            h = np.maximum(u[t] @ f(L["w_up"][e]), 0.0) ** 2
            out[t] += cfg.routed_scaling_factor * s[t, e] / total \
                * (h @ f(L["w_down"][e]))
    out += np.maximum(u @ f(L["shared"]["w_up"]), 0.0) ** 2 \
        @ f(L["shared"]["w_down"])
    want = norm(x + out, weights["final_norm"]) @ f(weights["head"])
    assert close(got, want, 1e-5)


def test_reference_agrees_with_the_zoo(built):
    cfg, model, params, ids, weights, hp = built
    want = np.asarray(model.apply({"params": params}, ids[None]))[0]
    got = np.asarray(nemotron_h_ref.forward_logits(weights, ids, hp))
    assert close(got, want, 1e-4)
    assert hp["kinds"] == ["mamba", "moe", "mamba", "attention", "moe",
                           "mamba", "moe"]
    rows = np.asarray([3, 23])
    part, margin, states = nemotron_h_ref.forward_logits(
        weights, ids, hp, rows=rows, with_margin=True, with_state=True)
    assert close(part, got[rows], 1e-6) and margin.shape == (2,)
    assert states.shape == (3, 4, 64, 128)


def test_weights_made_a_layer_at_a_time_are_the_models_tree():
    cfg, model, params = tiny()
    made = family().init_params(model, 7, jnp.float32)
    want = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), made)
    assert got == want
    again = family().init_params(model, 7, jnp.float32)
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree_util.tree_leaves(made), jax.tree_util.tree_leaves(again)))
    a_log = np.asarray(made["layers_0"]["mixer"]["A_log"])
    assert (a_log >= 0).all() and (a_log <= np.log(16)).all()
    bias = np.asarray(made["layers_1"]["mixer"]["e_score_correction_bias"])
    assert np.ptp(a_log) > 0 and 0 < np.abs(bias).max() < 0.5


@pytest.mark.parametrize("change", [
    {"drop": ("conv_history",)}, {"drop": ("D",)}, {"drop": ("gate",)},
    {"drop": ("gate_norm",)}, {"drop": ("shared",)}, {"drop": ("bias",)},
    {"drop": ("route_scale",)}, {"norm_before_gate": True},
    {"one_group": True}, {"gelu": True},
    {"norm_over_held": True, "held": (2, 4)}])
def test_reference_changes_when(built, change):
    """Each part is in the numbers: leaving it out, or taking the other
    reading of it, moves the logits by far more than any tolerance a check
    holds."""
    _, _, _, ids, weights, hp = built
    base = dict(hp, held=change.get("held"))
    if base["held"]:
        weights = dict(weights, layers=[
            {**l, **{k: l[k][2:6] for k in ("w_up", "w_down") if k in l}}
            for l in weights["layers"]])
    want = np.asarray(nemotron_h_ref.forward_logits(weights, ids, base))
    got = np.asarray(nemotron_h_ref.forward_logits(weights, ids,
                                                   dict(base, **change)))
    assert not close(got, want, 5e-3)


def test_lower_precision_moves_the_reference(built):
    """The order the chip's check rests on: float8 activations move the
    logits more than bfloat16 ones; a bfloat16 state moves the state it
    leaves and a float32 one does not."""
    _, _, _, ids, weights, hp = built
    want, states = nemotron_h_ref.forward_logits(weights, ids, hp,
                                                 with_state=True)
    err = lambda **kw: float(np.max(np.abs(np.asarray(
        nemotron_h_ref.forward_logits(weights, ids, hp, **kw)) - want))
        / np.max(np.abs(want)))
    bf16, f8 = err(act_dtype=jnp.bfloat16), err(act_dtype=jnp.float8_e4m3fn)
    assert 0 < bf16 < f8 and f8 > 4 * bf16
    low = nemotron_h_ref.forward_logits(weights, ids, hp, with_state=True,
                                        state_dtype=jnp.bfloat16)[1]
    rms = lambda a, b: float(np.sqrt(np.mean((np.asarray(a[0]) - b[0]) ** 2)
                                     / np.mean(np.asarray(b[0]) ** 2)))
    assert rms(low, np.asarray(states)) > 1e-3
    one_walk = nemotron_h_ref.forward_variants(weights, ids, hp, [
        {}, {"act_dtype": jnp.bfloat16, "head": False,
             "state_dtype": jnp.bfloat16}])
    assert np.array_equal(np.asarray(one_walk[0][0]), np.asarray(want))
    assert one_walk[1][0] is None and one_walk[1][2].shape == states.shape


def test_margin_is_the_nearest_held_experts_distance_in_the_biased_scores():
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.standard_normal((40, 32)), jnp.float32)
    layer = {"router": jnp.asarray(rng.standard_normal((32, 8)), jnp.float32),
             "bias": jnp.asarray(rng.normal(0, 0.1, 8), jnp.float32)}
    hp = {"top_k": 3, "held": (4, 4), "route_scale": 2.5}
    dense, margin, is_held = nemotron_h_ref.route(h, layer, hp)
    s = 1 / (1 + np.exp(-np.asarray(h @ layer["router"], np.float64)))
    biased = s + np.asarray(layer["bias"])
    top = -np.sort(-biased, axis=-1)
    by_hand = np.min(np.where(
        biased[:, 4:] >= top[:, 2:3], biased[:, 4:] - top[:, 3:4],
        top[:, 2:3] - biased[:, 4:]), axis=-1)
    assert np.allclose(margin, by_hand, atol=1e-6)
    assert np.allclose(np.asarray(dense).sum(-1), 2.5, atol=1e-5)
    assert (np.asarray(dense) > 0).sum(-1).tolist() == [3] * 40
    # weighed by the score, not by score + bias
    picked = np.asarray(dense) > 0
    assert np.allclose(np.asarray(dense)[picked].reshape(40, 3),
                       2.5 * s[picked].reshape(40, 3)
                       / (s * picked).sum(-1, keepdims=True), atol=1e-5)


# --------------------------------------------------------------------------- #
# the configuration file and the cell
# --------------------------------------------------------------------------- #

def test_the_registry_sees_the_cell():
    reg = Registry()
    cell = reg.cell(CELL)
    assert cell["driver"] == "serve_closed_state_moe" and cell["chips"] == 1
    assert cell["reports"] == ["serve_tok_s", "setup_s"]
    assert cell["trace_tail_s"] == 6.0 and cell["trace_seconds"] == 2.0
    cfg = reg.config(cell["config"])
    assert cfg["family"] == "nemotron_h"
    mix = reg.traffic(cell["traffic"])
    assert mix["clients"] == 128 and mix["pool_requests"] == 1024
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 768,
                                    "sigma": 1.0, "min": 64, "max": 8192}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 512,
                                    "max": 3072}
    assert mix["ramp_s"] == 15.0 and mix["warmup"]["requests"] == 128
    assert {m["name"] for m in reg.metrics_of(CELL, "end_to_end")} \
        == {"serve_tok_s", "setup_s"}
    assert callable(reg.driver(cell["driver"]))
    # the same traffic file as the Jamba cell: one queue, two kinds of layer
    assert sum(w["traffic"] == cell["traffic"]
               for w in reg.benchmark["workloads"]) == 2
    assert sum(w["chips"] == 4 for w in reg.benchmark["workloads"]) == 1


def test_the_cells_entries_are_appended_and_within_the_files_limits():
    """The configuration and the cell are appended entries; the cell reports
    the three per-layer metrics that list it alone — all the room the
    benchmark's limit of 128 per-layer metrics left (it had 125) — each
    entry equal to its file."""
    reg = Registry()
    bench = reg.benchmark
    names = [w["name"] for w in bench["workloads"]]
    assert CELL in names and names.index(CELL) >= 9
    config = [c for c in bench["configs"] if c["name"] == CELL.split(".")[0]]
    assert config and config[0]["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"]
    assert len(config[0]["why"]) <= 200
    assert len(bench["per_layer"]) <= 128
    mine = reg.metrics_of(CELL, "per_layer")
    assert {m["name"] for m in mine} == {
        "decode_step_ms.think", "moe_experts_share.think",
        "ssm_step_share.think"}
    for metric in mine:
        assert metric["workloads"] == [CELL] and metric["moves"] == "serve_tok_s"
        spec = reg.layer_metric(metric["name"])
        for k in ("layer", "moves", "unit", "workloads"):
            assert spec[k] == metric[k], (metric["name"], k)
    experts = reg.layer_metric("moe_experts_share.think")
    assert experts["args"] == {"scope": "moe_ffn/experts",
                               "instructions": ["%ragged-dot"]}
    tok_s = [m for m in bench["end_to_end"] if m["name"] == "serve_tok_s"][0]
    assert CELL in tok_s["workloads"]


def test_result_line_of_the_cell():
    """``test_registry.py::test_result_line_of_each_cell`` for this cell, by
    name: the plain line carries its end-to-end metrics, the traced line
    each per-layer metric a reader can give from the recorded tiny trace."""
    from types import SimpleNamespace
    from chipbench import harness
    reg = Registry()
    dev = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite",
                          memory_stats=lambda: {"peak_bytes_in_use": 5 << 30})
    entry = reg.cell(CELL)

    class AnyCounter(dict):
        get = lambda self, key, default=None: 1.0
        __getitem__ = lambda self, key: 1.0

    ctx = harness.Context(
        registry=reg, cell=entry, config=reg.config(entry["config"]),
        traffic=reg.traffic(entry["traffic"]), seed=1, seconds=1.0,
        devices=[dev], peaks=json.load(open(os.path.join(
            ROOT, "chipbench", "peaks.json")))["TPU v5 lite"],
        compiles=None, t_process=0.0, on_chip=False,
        tracer=SimpleNamespace(path=os.path.join(
            os.path.dirname(__file__), "data", "tiny_trace.xplane.pb")))
    out = harness.Outcome(correct=True, attempted=3, failed=0,
                          window_start=2.5, counters=AnyCounter(),
                          end_to_end={"serve_tok_s": 1.0})
    line = harness.result_line(ctx, out, trace=False)
    assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    traced = harness.result_line(ctx, out, trace=True)
    per_layer = {m["name"] for m in reg.metrics_of(CELL, "per_layer")}
    assert set(traced["metrics"]) <= per_layer


def test_published_keys_are_the_catalogs():
    """Every number of the catalog's config under its own key; what is cut
    is the depth, the pattern, the count of experts held and the vocabulary,
    with the published values beside them."""
    cfg = json.load(open(CONFIG))
    want = dict(hidden_size=2688, intermediate_size=1856,
                moe_intermediate_size=1856,
                moe_shared_expert_intermediate_size=3712,
                num_attention_heads=32, num_key_value_heads=2, head_dim=128,
                num_experts_per_tok=6, mamba_num_heads=64, mamba_head_dim=64,
                ssm_state_size=128, n_groups=8, conv_kernel=4, chunk_size=128,
                expand=2, n_group=1, topk_group=1, n_shared_experts=1,
                routed_scaling_factor=2.5, norm_eps=1e-05,
                layer_norm_epsilon=1e-05, rope_theta=10000,
                partial_rotary_factor=1, max_position_embeddings=262144,
                num_logits_to_keep=1, time_step_min=0.001,
                time_step_max=0.1, time_step_floor=0.0001)
    assert {k: cfg[k] for k in want} == want
    assert cfg["mlp_hidden_act"] == "relu2" and cfg["norm_topk_prob"] is True
    assert cfg["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                              "n_routed_experts", "vocab_size"]
    pub = cfg["published"]
    assert (cfg["num_hidden_layers"], pub["num_hidden_layers"]) == (16, 52)
    assert (cfg["n_routed_experts"], pub["n_routed_experts"]) == (64, 128)
    assert (cfg["vocab_size"], pub["vocab_size"]) == (65536, 131072)
    pattern = pub["hybrid_override_pattern"]
    assert cfg["hybrid_override_pattern"] == pattern[:16] == "MEMEM*EMEMEM*EME"
    assert (len(pattern), pattern.count("M"), pattern.count("E"),
            pattern.count("*")) == (52, 23, 23, 6)
    assert cfg["deployment"]["expert_parallel"] == 2
    assert family().experts(cfg) == (128, (0, 64))
    # within the guide's floors: 8 experts or more, an eighth of the
    # vocabulary or more, every kind of block in the cut
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= pub["vocab_size"]
    assert set(cfg["hybrid_override_pattern"]) == {"M", "E", "*"}
    assert set(cfg["assumed"]) >= {"no_position_embedding",
                                   "mamba_inner_width", "recurrence_dtype",
                                   "experts", "weights_init"}


def test_memory_account_recomputed_from_the_files_keys():
    """The configuration's account, from its own keys: the weights' count
    (at the published expert width), what a sequence costs the state pool,
    what is left for pages."""
    from deepspeed_tpu.inference.v2.ragged.kv_cache import KVCacheConfig
    from deepspeed_tpu.inference.v2.ragged.state_pool import StatePoolConfig
    cfg = json.load(open(CONFIG))
    fam, acc = family(), cfg["memory_account_numbers"]
    model = fam.build_model(cfg, jnp.bfloat16)
    shapes = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    count = sum(int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(shapes))
    assert 2 * count == acc["weight_bytes"] == 10_565_068_416
    assert shapes["layers_1"]["mixer"]["w_up"].shape == (64, 2688, 1856)
    state = fam.state_layout(cfg)
    assert state == {"layers": 7, "d_inner": 4096, "d_state": 128,
                     "d_conv": 4, "conv_dim": 6144, "conv_width": 6144,
                     "bytes_per_sequence": acc["state_bytes_a_sequence"]}
    assert acc["state_bytes_a_sequence"] \
        == 7 * (128 * 64 * 64 * 4 + 3 * 6144 * 4)
    sm = cfg["engine"]["state_manager"]
    pool = StatePoolConfig(7, sm["max_tracked_sequences"], 4096, 128, 4,
                           conv_dim=6144)
    assert pool.bytes_per_slot() == acc["state_bytes_a_sequence"]
    assert pool.total_bytes() == acc["state_pool_bytes"] \
        == acc["state_slots"] * acc["state_bytes_a_sequence"]
    assert acc["state_slots"] == sm["max_tracked_sequences"] + 1 == 145
    budget = int(acc["hbm_limit_bytes"] * cfg["hbm_fill"]) \
        - acc["weight_bytes"] - acc["state_pool_bytes"] \
        - cfg["hbm_headroom_bytes"]
    assert budget == acc["page_budget_bytes"]
    layers, heads, dim = fam.kv_layout(cfg)
    assert (layers, heads, dim) == (2, 2, 128)
    kv = KVCacheConfig.from_memory_budget(
        layers, heads, dim, budget,
        block_size=cfg["engine"]["kv_cache"]["block_size"],
        dtype=jnp.bfloat16)
    assert kv.bytes_per_block() == acc["bytes_a_page"] \
        == 128 * 2 * 2 * 2 * 128 * 2
    assert kv.num_blocks == acc["pages"]
    assert acc["tokens"] == acc["pages"] * 128
    # rows and state slots bind, not pages: the traffic's mean request
    # (1.27k of prompt + 1.79k of output) for every tracked sequence fits
    assert sm["max_tracked_sequences"] * (1270 + 1790) < acc["tokens"]
    assert sm["max_ragged_batch_size"] == sm["max_ragged_sequence_count"] \
        + 4 * sm["prefill_chunk_size"]
    # the fullest device: weights and both pools are over a quarter of it
    assert (acc["weight_bytes"] + acc["state_pool_bytes"]
            + acc["pages"] * acc["bytes_a_page"]) > acc["hbm_limit_bytes"] / 4


def test_kernel_work_at_the_published_widths():
    """A decode step's experts: 64 touched matrices of 2688 x 1856 bfloat16 a
    product, 0.78 ms at a v5e's 819 GB/s, whatever the stored padding; a
    decode row of a Mamba layer: 4 MiB of state and 144 KiB of tail and 8
    pairs of B and C."""
    from chipbench.reduce import mla_work, moe_work, ssd_work
    peaks = json.load(open(os.path.join(ROOT, "chipbench", "peaks.json")))
    v5e = peaks["TPU v5 lite"]
    flops, bytes_ = moe_work.grouped_product(384, 64, 2688, 1856)
    assert bytes_ == (64 * 2688 * 1856 + 384 * (2688 + 1856)) * 2
    assert flops == 2 * 384 * 2688 * 1856
    floor = mla_work.roofline(flops, bytes_, 1.0, v5e)
    assert floor["bound"] == "memory" and 0.77e-3 < floor["memory_s"] < 0.79e-3
    layer = moe_work.expert_layer(384, 64, 2688, 1856)
    assert layer == (2 * flops, 2 * bytes_)
    # a prefill pass's 3,072 assignments on the held experts: compute
    flops, bytes_ = moe_work.grouped_product(1024 * 3, 64, 2688, 1856)
    assert mla_work.roofline(flops, bytes_, 1.0, v5e)["bound"] == "memory" \
        or flops / v5e["bf16_flops_per_s"] > bytes_ / v5e["hbm_bytes_per_s"]
    one = ssd_work.decode_call(1, 4096, 128, 6144, 4)
    flops, bytes_ = moe_work.ssd_decode_call(1, 4096, 128, 6144, 4, 8)
    assert flops == one[0] == 4 * 128 * 4096
    assert bytes_ == one[1] + 2 * 7 * 128 * 4
    assert moe_work.ssd_decode_call(1, 4096, 128, 6144, 4, 1) == one
    flops, bytes_ = moe_work.ssd_scan_call(1024, 4, 64, 64, 128, 128, 8)
    assert flops == 1024 * ssd_work.scan_token_flops(64, 64, 128, 128, 8)
    assert flops > ssd_work.scan_call(1024, 4, 64, 64, 128, 128)[0]
    assert moe_work.ssd_scan_call(1024, 4, 64, 64, 128, 128, 1) \
        == ssd_work.scan_call(1024, 4, 64, 64, 128, 128)


def test_roofline_tool_reads_the_grouped_products_and_both_ssd_kernels():
    """The tool's reduction on the trace written by hand: the Pallas grouped
    matmul's call inside a decode step counts under the decode programs
    only, and the widths come from either family's configuration."""
    from tests.chipbench.test_named import hand_trace
    tool = Registry().module("tools", "moe_roofline")
    cfg = json.load(open(CONFIG))
    call = "jit(serve_{})/while/body/closed_call/{}/pallas_call"
    names = {"jit_serve_decode_step(1)": {
                 "closed_call.21": call.format(
                     "decode_step", "ffn/moe_ffn/experts/moe_grouped_matmul")},
             "jit_serve_prefill_packed(2)": {
                 "closed_call.7": call.format("prefill_packed",
                                              "ssm/scan/ssd_chunk_scan")}}
    peaks = json.load(open(os.path.join(ROOT, "chipbench", "peaks.json")))[
        "TPU v5 lite"]
    view = {"trace": hand_trace(), "op_names": names, "peaks": peaks}
    assert list(tool.grouped_calls(view["trace"], names, tool.DECODE)) == [200]
    assert list(tool.grouped_calls(view["trace"], names, tool.PREFILL)) == []
    got = tool.shares_of(view, cfg, [(128, 0), (126, 0)], 0.99)
    step = [v for k, v in got.items()
            if k.startswith("grouped product (decode steps")][0]
    assert step["calls"] == 1 and step["rows"] == 127 * 3
    assert step["experts_touched"] == pytest.approx(0.99 * 64)
    assert step["bound"] == "memory" and step["us_a_call"] == pytest.approx(0.2)
    scan = [v for k, v in got.items() if k.startswith("ssd_chunk_scan")][0]
    assert scan["rows"] == 1024 and scan["groups"] == 8
    assert tool.widths(cfg) == {
        "heads": 64, "d_head": 64, "d_inner": 4096, "d_state": 128,
        "d_conv": 4, "groups": 8, "conv_width": 6144, "chunk": 128,
        "hidden": 2688, "width": 1856, "held": 64, "routed": 128, "top_k": 6}
    granite = json.load(open(os.path.join(
        ROOT, "chipbench", "configs", "granite4-h-small-serve-ep2.json")))
    w = tool.widths(granite)
    assert (w["groups"], w["width"], w["held"], w["routed"], w["d_inner"]) \
        == (1, 768, 36, 72, 8192)
