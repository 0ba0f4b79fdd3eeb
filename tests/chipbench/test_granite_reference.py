"""The plain reference of the granite family
(``chipbench/reference/granite_ref.py``), its configuration file's arithmetic,
the family module and the work functions of the two SSD kernels."""

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
from chipbench.reference import granite_ref  # noqa: E402

CONFIG = os.path.join(ROOT, "chipbench", "configs",
                      "granite4-h-small-serve-ep2.json")
CELL = "granite4-h-small-serve-ep2" + ".docqa-closed"


def family():
    return Registry().module("families", "granite")


def tiny(**kw):
    from deepspeed_tpu.models.granite import (GraniteConfig,
                                              GraniteForCausalLM)
    cfg = GraniteConfig.tiny(dtype=jnp.float32, **kw)
    model = GraniteForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(3),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, model, params


def as_file(cfg):
    fam = family()
    d = {k: getattr(cfg, k) for k in fam.MODEL_KEYS}
    first, count = cfg.held
    d.update(num_local_experts=count, deployment={"held_first": first},
             published={"num_local_experts": cfg.num_local_experts})
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


def test_two_tokens_of_one_mamba_layer_by_hand():
    """Section 1 of the issue on two tokens, written out with numpy: the
    convolution over its zero history, the state after one and after two
    tokens, the gate before the norm, the multipliers."""
    cfg, _, params = tiny(num_hidden_layers=1, layer_types=("mamba",),
                          mamba_n_heads=4)
    weights, hp = weights_hp(cfg, params)
    ids = np.asarray([5, 9], np.int32)
    got = np.asarray(granite_ref.forward_logits(weights, ids, hp))
    f = lambda a: np.asarray(a, np.float64)
    L = {k: (f(v) if not isinstance(v, dict) else {a: f(b) for a, b in v.items()})
         for k, v in weights["layers"][0].items()}
    eps, r = cfg.rms_norm_eps, cfg.residual_multiplier
    norm = lambda v, w: v / np.sqrt((v * v).mean(-1, keepdims=True) + eps) * w
    silu = lambda v: v / (1 + np.exp(-v))
    softplus = lambda v: np.log1p(np.exp(v))
    H, P, N = 4, 64, 128
    E = H * P
    x = 12.0 * f(weights["embed"])[ids]
    zxd = norm(x, L["ln_in"]) @ L["w_in"]
    z, xbc, dt = zxd[:, :E], zxd[:, E:2 * E + 2 * N], zxd[:, 2 * E + 2 * N:]
    w = L["conv_w"]                                         # [W, 4]
    c0 = silu(L["conv_b"] + xbc[0] * w[:, 3])
    c1 = silu(L["conv_b"] + xbc[0] * w[:, 2] + xbc[1] * w[:, 3])
    a, ys, S = -np.exp(L["A_log"]), [], np.zeros((H, P, N))
    for c, d in ((c0, dt[0]), (c1, dt[1])):
        X, B, C = c[:E].reshape(H, P), c[E:E + N], c[E + N:]
        d = softplus(d + L["b_dt"])
        S = np.exp(d * a)[:, None, None] * S \
            + (d[:, None] * X)[:, :, None] * B[None, None, :]
        ys.append((S @ C + L["D"][:, None] * X).reshape(E))
    g = np.stack(ys) * silu(z)
    x = x + r * (norm(g, L["g_norm"]) @ L["w_out"])
    h2 = norm(x, L["ln_ff"])
    logits = h2 @ L["router"]
    moe = np.zeros_like(h2)
    for t in range(2):
        top = np.argsort(-logits[t])[:cfg.num_experts_per_tok]
        wts = np.exp(logits[t][top] - logits[t][top].max())
        wts /= wts.sum()
        for e, wt in zip(top, wts):
            moe[t] += wt * ((silu(h2[t] @ L["w_gate"][e])
                             * (h2[t] @ L["w_up"][e])) @ L["w_down"][e])
    s = L["shared"]
    shared = (silu(h2 @ s["w_gate"]) * (h2 @ s["w_up"])) @ s["w_down"]
    x = x + r * (moe + shared)
    want = norm(x, f(weights["final_norm"])) @ f(weights["embed"]).T / 16.0
    assert close(got, want, 2e-5)


def test_reference_agrees_with_the_zoo(built):
    cfg, model, params, ids, weights, hp = built
    want = np.asarray(model.apply({"params": params}, ids[None]))[0]
    got = np.asarray(granite_ref.forward_logits(weights, ids, hp))
    assert close(got, want, 1e-4)
    assert hp["kinds"] == ["mamba", "attention", "mamba", "mamba"]
    rows = np.asarray([3, 23])
    part, margin, states = granite_ref.forward_logits(
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
    a_log = np.asarray(made["layers_0"]["mamba"]["A_log"])
    assert (a_log >= 0).all() and (a_log <= np.log(16)).all()
    assert np.ptp(a_log) > 0


@pytest.mark.parametrize("change", [
    {"drop": ("conv_history",)}, {"drop": ("D",)}, {"drop": ("gate",)},
    {"drop": ("gate_norm",)}, {"drop": ("shared",)},
    {"norm_before_gate": True}, {"softmax_over_held": True, "held": (2, 4)},
    {"attn_scale": 128 ** -0.5}, {"residual_scale": 1.0},
    {"embed_scale": 1.0}, {"logits_scaling": 1.0}])
def test_reference_changes_when(built, change):
    """Each part and each multiplier is in the numbers: leaving it out, or
    taking the other reading of it, moves the logits by far more than any
    tolerance a check holds."""
    _, _, _, ids, weights, hp = built
    base = dict(hp, held=change.get("held"))
    if base["held"]:
        weights = dict(weights, layers=[
            {**l, **{k: l[k][2:6] for k in ("w_gate", "w_up", "w_down")}}
            for l in weights["layers"]])
    want = np.asarray(granite_ref.forward_logits(weights, ids, base))
    got = np.asarray(granite_ref.forward_logits(weights, ids,
                                                dict(base, **change)))
    assert not close(got, want, 5e-3)


def test_lower_precision_moves_the_reference(built):
    """The order the chip's check rests on: float8 activations move the
    logits more than bfloat16 ones; a bfloat16 state moves the state it
    leaves and a float32 one does not."""
    _, _, _, ids, weights, hp = built
    want, states = granite_ref.forward_logits(weights, ids, hp,
                                              with_state=True)
    err = lambda **kw: float(np.max(np.abs(np.asarray(
        granite_ref.forward_logits(weights, ids, hp, **kw)) - want))
        / np.max(np.abs(want)))
    bf16, f8 = err(act_dtype=jnp.bfloat16), err(act_dtype=jnp.float8_e4m3fn)
    assert 0 < bf16 < f8 and f8 > 4 * bf16
    low = granite_ref.forward_logits(weights, ids, hp, with_state=True,
                                     state_dtype=jnp.bfloat16)[1]
    rms = lambda a, b: float(np.sqrt(np.mean((np.asarray(a[0]) - b[0]) ** 2)
                                     / np.mean(np.asarray(b[0]) ** 2)))
    assert rms(low, np.asarray(states)) > 1e-3
    one_walk = granite_ref.forward_variants(weights, ids, hp, [
        {}, {"act_dtype": jnp.bfloat16, "head": False,
             "state_dtype": jnp.bfloat16}])
    assert np.array_equal(np.asarray(one_walk[0][0]), np.asarray(want))
    assert one_walk[1][0] is None and one_walk[1][2].shape == states.shape


def test_margin_is_the_nearest_held_experts_distance_from_changing_sides():
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.standard_normal((40, 32)), jnp.float32)
    layer = {"router": jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)}
    hp = {"top_k": 3, "held": (4, 4)}
    dense, margin, is_held = granite_ref.route(h, layer, hp)
    logits = np.asarray(h @ layer["router"])
    top = -np.sort(-logits, axis=-1)
    by_hand = np.min(np.where(
        logits[:, 4:] >= top[:, 2:3], logits[:, 4:] - top[:, 3:4],
        top[:, 2:3] - logits[:, 4:]), axis=-1)
    assert np.allclose(margin, by_hand, atol=1e-6)
    gap = top[:, 2] - top[:, 3]
    assert (np.asarray(margin) >= gap - 1e-6).all()
    assert np.allclose(np.asarray(dense).sum(-1), 1.0, atol=1e-6)
    assert (np.asarray(dense) > 0).sum(-1).tolist() == [3] * 40
    every = granite_ref.route(h, layer, {"top_k": 3, "held": None})[1]
    assert np.allclose(every, gap, atol=1e-6)


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
    assert cfg["family"] == "granite"
    mix = reg.traffic(cell["traffic"])
    assert mix["clients"] == 64 and mix["pool_requests"] == 512
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 1536,
                                    "sigma": 1.0, "min": 256, "max": 8192}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 384,
                                    "max": 1536}
    names = {m["name"] for m in reg.metrics_of(CELL, "per_layer")}
    assert {"ssm_share.docqa", "ssm_scan_share.docqa", "ssm_step_share.docqa",
            "ssm_gate_norm_share.docqa", "moe_ffn_share.docqa",
            "attn_full_share.docqa", "state_slots_peak_share.docqa",
            "decode_rows_mean.docqa"} <= names
    assert {m["name"] for m in reg.metrics_of(CELL, "end_to_end")} \
        == {"serve_tok_s", "setup_s"}
    assert callable(reg.driver(cell["driver"]))
    assert len(reg.benchmark["workloads"]) == 9
    assert sum(w["chips"] == 4 for w in reg.benchmark["workloads"]) == 1


def test_the_cells_entries_are_the_last_and_its_metrics_its_own():
    """The configuration and the cell are the last entries of their lists,
    and the cell reports the 17 per-layer metrics that list it alone, each
    entry equal to its file (``test_registry.py``'s own rule)."""
    reg = Registry()
    bench = reg.benchmark
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == bench["workloads"][-1]["config"]
    assert bench["configs"][-1]["reduced"] == [
        "num_hidden_layers", "num_local_experts", "layer_types"]
    assert len(bench["configs"][-1]["why"]) <= 200
    mine = reg.metrics_of(CELL, "per_layer")
    assert len(mine) == 17
    assert bench["per_layer"][-17:] == mine
    for metric in mine:
        assert metric["workloads"] == [CELL] and metric["moves"] == "serve_tok_s"
        spec = reg.layer_metric(metric["name"])
        for k in ("layer", "moves", "unit", "workloads"):
            assert spec[k] == metric[k], (metric["name"], k)
    tok_s = [m for m in bench["end_to_end"] if m["name"] == "serve_tok_s"][0]
    assert tok_s["workloads"][-1] == CELL


def test_the_set_up_metrics_lists_are_the_accepted_files():
    """The ten metrics that move ``setup_s`` keep the eight cells their
    accepted files list: appending this cell there edits those files, which
    is a ``benchmark`` PR's to do. Until one does,
    ``test_totals_reader.py::test_every_cell_reports_eight_of_them`` fails on
    this cell and on nothing else (PERF.md section 7)."""
    reg = Registry()
    setup = [m for m in reg.benchmark["per_layer"] if m["moves"] == "setup_s"]
    assert len(setup) == 10
    for metric in setup:
        assert CELL not in metric["workloads"]
        assert reg.layer_metric(metric["name"])["workloads"] \
            == metric["workloads"]
    for w in reg.benchmark["workloads"][:-1]:
        assert sum(w["name"] in m["workloads"] for m in setup) == 8


def test_result_line_of_the_cell():
    """``test_registry.py::test_result_line_of_each_cell`` for this cell,
    by name: the plain line carries its end-to-end metrics, the traced line
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
    absent = per_layer - set(traced["metrics"])
    assert all(reg.layer_metric(n)["reader"] == "trace.module_ms"
               or reg.layer_metric(n)["reader"].split(".")[0]
               in ("named", "span") for n in absent)
    assert {"decode_rows_mean.docqa", "state_slots_peak_share.docqa",
            "kv_pages_peak_share.docqa", "compiles_in_window.docqa"} \
        <= set(traced["metrics"])


def test_published_keys_are_the_catalogs():
    """Every number of the catalog's config under its own key; what is cut
    is the depth, the layer list and the count of experts held, with the
    published values beside them."""
    cfg = json.load(open(CONFIG))
    want = dict(hidden_size=4096, intermediate_size=768,
                shared_intermediate_size=1536, num_attention_heads=32,
                num_key_value_heads=8, vocab_size=100352,
                num_experts_per_tok=10, mamba_n_heads=128, mamba_d_head=64,
                mamba_d_state=128, mamba_n_groups=1, mamba_d_conv=4,
                mamba_expand=2, mamba_chunk_size=256,
                attention_multiplier=0.0078125, embedding_multiplier=12,
                residual_multiplier=0.22, logits_scaling=16,
                rms_norm_eps=1e-05, rope_theta=10000,
                max_position_embeddings=131072)
    assert {k: cfg[k] for k in want} == want
    assert cfg["reduced"] == ["num_hidden_layers", "num_local_experts",
                              "layer_types"]
    pub = cfg["published"]
    assert (cfg["num_hidden_layers"], pub["num_hidden_layers"]) == (10, 40)
    assert (cfg["num_local_experts"], pub["num_local_experts"]) == (36, 72)
    assert cfg["layer_types"] == pub["layer_types"][:10]
    assert [i for i, t in enumerate(pub["layer_types"])
            if t == "attention"] == [5, 15, 25, 35]
    assert cfg["deployment"]["expert_parallel"] == 2
    assert family().experts(cfg) == (72, (0, 36))
    # within the guide's floors: a whole period, 8 experts or more, the
    # vocabulary uncut
    assert cfg["num_hidden_layers"] >= 4 and cfg["num_local_experts"] >= 8


def test_memory_account_recomputed_from_the_files_keys():
    """The configuration's account, from its own keys: the weights' count,
    what a sequence costs the state pool, what is left for pages."""
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
    assert 2 * count == acc["weight_bytes"] == 9_925_465_344
    state = fam.state_layout(cfg)
    assert state == {"layers": 9, "d_inner": 8192, "d_state": 128,
                     "d_conv": 4, "conv_dim": 8448, "conv_width": 9216,
                     "bytes_per_sequence": acc["state_bytes_a_sequence"]}
    assert acc["state_bytes_a_sequence"] \
        == 9 * (128 * 64 * 128 * 4 + 3 * 9216 * 4)
    sm = cfg["engine"]["state_manager"]
    pool = StatePoolConfig(9, sm["max_tracked_sequences"], 8192, 128, 4,
                           conv_dim=8448)
    assert pool.bytes_per_slot() == acc["state_bytes_a_sequence"]
    assert pool.total_bytes() == acc["state_pool_bytes"] \
        == acc["state_slots"] * acc["state_bytes_a_sequence"]
    assert acc["state_slots"] == sm["max_tracked_sequences"] + 1
    budget = int(acc["hbm_limit_bytes"] * cfg["hbm_fill"]) \
        - acc["weight_bytes"] - acc["state_pool_bytes"] \
        - cfg["hbm_headroom_bytes"]
    assert budget == acc["page_budget_bytes"]
    layers, heads, dim = fam.kv_layout(cfg)
    assert (layers, heads, dim) == (1, 8, 128)
    kv = KVCacheConfig.from_memory_budget(
        layers, heads, dim, budget,
        block_size=cfg["engine"]["kv_cache"]["block_size"],
        dtype=jnp.bfloat16)
    assert kv.bytes_per_block() == acc["bytes_a_page"] == 128 * 2 * 8 * 128 * 2
    assert kv.num_blocks == acc["pages"]
    assert acc["tokens"] == acc["pages"] * 128
    # rows and state slots bind, not pages: the traffic's mean request
    # (2.2k of prompt + 960 of output) for every tracked sequence fits
    assert sm["max_tracked_sequences"] * (2200 + 960) < acc["tokens"]
    assert sm["max_ragged_batch_size"] == sm["max_ragged_sequence_count"] \
        + 4 * sm["prefill_chunk_size"]


def test_kernel_work_at_the_published_widths():
    """A decode row: 8 MiB of state and 216 KiB of tail, 10.2 us at a v5e's
    819 GB/s; a prompt token a layer in the product form: 16.9 MFLOP."""
    from chipbench.reduce import mla_work, ssd_work
    flops, bytes_ = ssd_work.decode_call(1, 8192, 128, 9216, 4)
    assert bytes_ == 2 * 4 * (128 * 8192 + 3 * 9216) + (3 * 8192 + 256) * 4
    assert flops == 4 * 128 * 8192
    peaks = json.load(open(os.path.join(ROOT, "chipbench", "peaks.json")))
    v5e = peaks["TPU v5 lite"]
    floor = mla_work.roofline(flops, bytes_, 1.0, v5e)
    assert floor["bound"] == "memory"
    assert 10.2e-6 < floor["memory_s"] < 10.8e-6
    per_token = ssd_work.scan_token_flops(128, 64, 128, 256)
    assert per_token == 2 * 256 * 128 + 128 * (2 * 256 * 64 + 4 * 128 * 64)
    assert 8.4e6 < per_token < 8.6e6
    flops, bytes_ = ssd_work.scan_call(1024, 4, 128, 64, 128, 256)
    assert flops == 1024 * per_token
    assert mla_work.roofline(flops, bytes_, 1.0, v5e)["bound"] == "memory" \
        or flops / v5e["bf16_flops_per_s"] > bytes_ / v5e["hbm_bytes_per_s"]


def test_roofline_tool_reads_both_kernels_calls():
    """The tool's reduction on the trace written by hand: the decode kernel's
    call inside a decode step and the scan's inside a prefill program count,
    each under its own programs only."""
    from tests.chipbench.test_named import hand_trace
    tool = Registry().module("tools", "ssd_roofline")
    cfg = json.load(open(CONFIG))
    call = "jit(serve_{})/while/body/closed_call/ssm/{}/pallas_call"
    names = {"jit_serve_decode_step(1)": {
                 "closed_call.21": call.format("decode_step",
                                               "step/ssd_decode_step")},
             "jit_serve_prefill_packed(2)": {
                 "closed_call.7": call.format("prefill_packed",
                                              "scan/ssd_chunk_scan")}}
    peaks = json.load(open(os.path.join(ROOT, "chipbench", "peaks.json")))[
        "TPU v5 lite"]
    view = {"trace": hand_trace(), "op_names": names, "peaks": peaks}
    assert list(tool.kernel_calls(view["trace"], names,
                                  "ssd_decode_step")) == [200]
    assert list(tool.kernel_calls(view["trace"], names,
                                  "ssd_chunk_scan")) == [200]
    swapped = {"jit_serve_decode_step(1)": names[
        "jit_serve_prefill_packed(2)"] | {"closed_call.21": names[
            "jit_serve_prefill_packed(2)"]["closed_call.7"]}}
    assert list(tool.kernel_calls(view["trace"], swapped,
                                  "ssd_chunk_scan")) == []
    got = tool.shares_of(view, cfg, [(64, 0), (62, 0)])
    step = got["ssd_decode_step"]
    assert step["calls"] == 1 and step["rows"] == 63
    assert step["bound"] == "memory"
    assert step["us_a_call"] == pytest.approx(0.2)
    scan = [v for k, v in got.items() if k.startswith("ssd_chunk_scan")][0]
    assert scan["rows"] == 1024 and scan["calls"] == 1
    assert tool.widths(cfg) == {"heads": 128, "d_head": 64, "d_inner": 8192,
                                "d_state": 128, "d_conv": 4,
                                "conv_width": 9216, "chunk": 256}
