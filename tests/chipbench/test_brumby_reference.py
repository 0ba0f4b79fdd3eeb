"""The plain reference for Brumby (``chipbench/reference/brumby_ref.py``): its
attention form against its own state form and against a few lines of numpy;
the zoo's module against it; its variants (rows, rounded activations, a
rounded state); the order of a state's entries the family hands it; and the
cell the configuration runs in: its files, its arithmetic, its traffic, its
metrics' readers and what they count. What this file says of
``BENCHMARK.json`` it says by membership, not by place: a later cell moves
nothing here."""

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
from chipbench.reduce import hlo_names, pr_work  # noqa: E402
from chipbench.reduce.xplane import DeviceTrace, Event, Trace  # noqa: E402
from chipbench.reference import brumby_ref as ref  # noqa: E402
from deepspeed_tpu.monitor.trace import Capture  # noqa: E402
from tests.chipbench.test_named import mosaic  # noqa: E402

CELL = "brumby-14b-serve-pp8.longdoc-closed-32"
CONFIG = "brumby-14b-serve-pp8"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
OWN = ("pr_share.longdoc32", "pr_scan_share.longdoc32",
       "pr_step_share.longdoc32", "pr_step_roofline_share.longdoc32",
       "pr_scan_roofline_share.longdoc32")
FOLDED = ("decode_step_ms.serve", "host_ms_per_step.serve",
          "decode_rows_mean.serve", "compiles_in_window.serve",
          "device_idle_share.serve", "prefill_device_share.serve",
          "engine_unaccounted_share.serve", "state_slots_peak_share.serve")
SETUP = ("setup_trace_s", "setup_lower_s", "setup_backend_s",
         "setup_programs", "traffic_compile_s", "setup_engine_init_s.serve",
         "setup_warmup_s.serve", "setup_program_share")


def family():
    return Registry().module("families", "brumby")


def tiny(seed=0):
    """Two layers at toy widths, every norm's gain moved off one, as
    (config, module, params, configuration-file keys)."""
    from deepspeed_tpu.models.brumby import BrumbyConfig, BrumbyForCausalLM
    cfg = BrumbyConfig.tiny(dtype=jnp.float32)
    model = BrumbyForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 1000))

    def shake(path, leaf):
        if any("norm" in getattr(p, "key", "") for p in path):
            return leaf + 0.2 * jax.random.normal(next(keys), leaf.shape)
        return leaf

    params = jax.tree_util.tree_map_with_path(shake, params)
    d = {k: getattr(cfg, k) for k in family().MODEL_KEYS}
    d["assumed_numbers"] = {
        "power": 2, "retention_eps": cfg.retention_eps,
        "chunk_size": cfg.chunk_size,
        "gate_init": [list(r) for r in cfg.gate_init]}
    return cfg, model, params, d


def close(got, want, tol=1e-4):
    got, want = np.asarray(got), np.asarray(want)
    return np.max(np.abs(got - want)) <= tol * np.max(np.abs(want))


@pytest.fixture(scope="module")
def model():
    return tiny()


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(0, 256, 70).astype(np.int32)


def weights_hp(model, **hp_over):
    _, _, params, d = model
    fam = family()
    return fam.reference_weights(params, d), {**fam.reference_hp(d),
                                              **hp_over}


# --------------------------------------------------------------------------- #
# the layer's two forms
# --------------------------------------------------------------------------- #

def numpy_retention_stepwise(q, k, v, lg, eps):
    """The state form a token at a time with the FULL ``d x d`` outer
    product of a key as the state's rows (no symmetry used): ``S += (k k^T)
    (x) v``, read through ``q q^T``."""
    q, k, v, lg = (np.asarray(x, np.float64) for x in (q, k, v, lg))
    T, Hk, G, d = q.shape
    S = np.zeros((Hk, d, d, d))
    z = np.zeros((Hk, d, d))
    out = np.zeros((T, Hk, G, d))
    for t in range(T):
        g = np.exp(lg[t])[:, None, None]
        kk = k[t][:, :, None] * k[t][:, None, :]
        S = g[..., None] * S + kk[..., None] * v[t][:, None, None, :]
        z = g * z + kk
        qq = q[t][:, :, :, None] * q[t][:, :, None, :]          # [Hk, G, d, d]
        out[t] = np.einsum("hgab,habc->hgc", qq, S) \
            / (np.einsum("hgab,hab->hg", qq, z)[..., None] + d * eps)
    return out


def test_the_attention_form_is_the_recurrence():
    """``retention`` (all pairs, a block of queries at a time) against the
    recurrence over the full outer product, and against ``recurrence`` over
    the upper triangle in both orders of its entries."""
    rng = np.random.default_rng(1)
    T, Hk, G, d = 37, 2, 2, 8
    q = rng.standard_normal((T, Hk, G, d)).astype(np.float32)
    k = rng.standard_normal((T, Hk, d)).astype(np.float32)
    v = rng.standard_normal((T, Hk, d)).astype(np.float32)
    lg = np.log(rng.uniform(0.5, 0.999, (T, Hk))).astype(np.float32)
    want = numpy_retention_stepwise(q, k, v, lg, 1e-6)
    block = ref.QUERY_BLOCK
    try:
        ref.QUERY_BLOCK = 16        # three blocks, the last one short
        got = ref.retention(*map(jnp.asarray, (q, k, v, lg)), 1e-6)
    finally:
        ref.QUERY_BLOCK = block
    assert close(got, want, 1e-5)
    from deepspeed_tpu.ops.pallas.power_retention import expansion
    for order in (ref.triangle(d), expansion(d)):
        y, S, z = ref.recurrence(*map(jnp.asarray, (q, k, v, lg)), order,
                                 1e-6)
        assert close(y, want, 1e-5)
        assert S.shape == (Hk, d, len(order[0])) and z.shape == S.shape[::2]


def test_the_forward_in_either_form_gives_the_same_logits(model, ids):
    weights, hp = weights_hp(model)
    plain = ref.forward_logits(weights, ids, hp)
    stated, states = ref.forward_logits(weights, ids, hp, with_state=True)
    assert close(stated, plain, 1e-5)
    # [L, D, Hk d + 8]: the pool's sublanes in whole eights
    assert states.shape == (2, 144, 2 * 16 + 8)
    assert not np.asarray(states)[..., 34:].any()


def test_the_zoo_module_is_the_reference(model, ids):
    _, module, params, _ = model
    weights, hp = weights_hp(model)
    want = ref.forward_logits(weights, ids, hp)
    got = module.apply({"params": params}, jnp.asarray(ids)[None])[0]
    assert close(got, want, 1e-5)


def test_rows_rounding_and_the_states_control(model, ids):
    """``rows`` picks logits; bfloat16 activations move them a little and a
    bfloat16 state moves the state more than they do; the head goes a block
    of columns at a time."""
    weights, hp = weights_hp(model)
    full = np.asarray(ref.forward_logits(weights, ids, hp))
    rows = np.asarray([3, 40, 69])
    block = ref.HEAD_BLOCK
    try:
        ref.HEAD_BLOCK = 100        # three blocks of the 256 columns
        some = ref.forward_logits(weights, ids, hp, rows=rows)
    finally:
        ref.HEAD_BLOCK = block
    assert close(some, full[rows], 1e-6)
    bf = jnp.bfloat16
    acts, s_act = ref.forward_logits(weights, ids, hp, with_state=True,
                                     act_dtype=bf)
    err = np.max(np.abs(np.asarray(acts) - full)) / np.max(np.abs(full))
    assert 1e-4 < err < 5e-2
    _, s_ctl = ref.forward_logits(weights, ids, hp, with_state=True,
                                  act_dtype=bf, state_dtype=bf)
    rms = lambda a, b: float(np.sqrt(np.mean((np.asarray(a) - b) ** 2)
                                     / np.mean(np.asarray(b) ** 2)))
    assert rms(s_ctl[0], np.asarray(s_act[0])) > 1e-3


def test_the_familys_order_is_the_programs_entries():
    """The program interleaves a head's halves, so its value ``c`` is the
    published value ``turn[c]``: the order the family hands the reference
    names, entry by entry, the pair the program's entry holds."""
    from deepspeed_tpu.ops.pallas.power_retention import expand
    d = 16
    i, j, m = family().expansion({"head_dim": d})
    turn = np.arange(d).reshape(2, d // 2).T.reshape(-1)
    a = np.random.default_rng(2).standard_normal(d).astype(np.float32)
    np.testing.assert_allclose(np.asarray(expand(jnp.asarray(a[turn]), True)),
                               a[i] * a[j] * m, rtol=1e-6)
    pairs = {(min(x, y), max(x, y)) for x, y, w in zip(i, j, m) if w}
    assert len(pairs) == d * (d + 1) // 2


# --------------------------------------------------------------------------- #
# the configuration, its cell and its metrics
# --------------------------------------------------------------------------- #

def test_the_registry_finds_the_cell_and_its_files():
    reg = Registry()
    cell = reg.cell(CELL)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert cell["driver"] == "serve_closed_slots"
    assert cell["trace_tail_s"] >= 6.0
    assert reg.config(CONFIG)["family"] == "brumby"
    names = {m["name"] for m in reg.metrics_of(CELL, "per_layer")}
    assert names == set(OWN) | set(FOLDED) | set(SETUP)
    # nothing of pages, experts or the other state tenants' kernels runs
    assert not [n for n in names if n.startswith((
        "kv_", "attn_", "paged_", "moe_", "ssm_", "gdn_", "mla_", "cca_"))]
    assert {m["name"] for m in reg.metrics_of(CELL, "end_to_end")} == {
        "serve_tok_s", "setup_s"}
    for name in names:
        spec = reg.layer_metric(name)
        assert callable(reg.reader(spec["reader"]))
    for name, scope in zip(OWN[:3], ("pr", "pr/scan", "pr/step")):
        spec = reg.layer_metric(name)
        assert spec["reader"] == "named.scope_share"
        assert spec["args"] == {"scope": scope}
    assert set(cell["layer_notes"]) == set(OWN) | set(FOLDED)
    assert callable(reg.driver(cell["driver"]))


def test_the_cell_is_an_entry_of_its_own_on_one_chip():
    cells = [w for w in BENCH["workloads"] if w["config"] == CONFIG]
    assert [w["name"] for w in cells] == [CELL] and cells[0]["chips"] == 1
    assert cells[0]["traffic"] == "longdoc-closed-32"
    (config,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["source"] == Registry().config(CONFIG)["source"] == (
        "https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/"
        "config.json")
    own = [m for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in own) == sorted(OWN)
    assert {(m["layer"], m["moves"], m["unit"], m["source"]) for m in own} \
        == {("power-retention kernels", "serve_tok_s", "%", "device_trace")}
    (tok_s,) = [m for m in BENCH["end_to_end"] if m["name"] == "serve_tok_s"]
    assert CELL in tok_s["workloads"]
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1


def test_the_scope_patterns_tell_the_new_scopes_apart():
    name = "jit(serve_paged_pass)/while/body/pr/{}/pallas_call"
    hit = lambda scope, op: bool(hlo_names.scope_pattern(scope).search(op))
    step, scan = name.format("step/pr_decode_step"), name.format(
        "scan/pr_chunk_scan")
    assert hit("pr", step) and hit("pr/step", step) and not hit("pr/scan",
                                                                step)
    assert hit("pr", scan) and hit("pr/scan", scan)
    assert hit("pr", name.format("qk_norm_rope")) \
        and not hit("pr/step", name.format("out_proj"))
    assert not hit("pr", "jit(x)/while/body/ffn/dot_general")
    readers = Registry().module("readers", "pr")
    assert hit(readers.STEP_SCOPE, step) and not hit(readers.STEP_SCOPE, scan)
    assert hit(readers.SCAN_SCOPE, scan) and not hit(readers.SCAN_SCOPE, step)


def test_the_traffic_is_the_issues():
    mix = Registry().traffic("longdoc-closed-32")
    assert (mix["kind"], mix["clients"], mix["pool_requests"], mix["ramp_s"],
            mix["drain_s"], mix["sampling"]) == (
                "serve_closed", 32, 256, 15.0, 120.0, "greedy")
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 4096,
                                    "sigma": 1.0, "min": 512, "max": 32768}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 256,
                                    "max": 1024}
    warm = mix["warmup"]
    assert warm["requests"] == 32
    assert warm["prompt_tokens"] == mix["prompt_tokens"]
    assert warm["output_tokens"] == {"dist": "uniform", "min": 8, "max": 40}
    # longdoc-closed.json's length distributions exactly, at half its clients
    full = Registry().traffic("longdoc-closed")
    for key in ("prompt_tokens", "output_tokens", "ramp_s", "sampling"):
        assert mix[key] == full[key]
    assert (full["clients"], full["pool_requests"]) == (64, 512)


def test_the_file_holds_the_published_config_but_the_depth():
    """The catalog row's ``config``, key for key, but ``num_hidden_layers``
    (``/opt/skills/guides/model-configs/architectures.jsonl``, row
    Brumby-14B-Base, copied here)."""
    cfg = Registry().config(CONFIG)
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 5120, "intermediate_size": 17408,
        "max_position_embeddings": 32768, "max_window_layers": 40,
        "model_type": "brumby", "num_attention_heads": 40,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    differ = {k for k, v in published.items() if cfg[k] != v}
    assert differ == {"num_hidden_layers"} == set(cfg["reduced"])
    assert cfg["num_hidden_layers"] == 5
    assert cfg["published"] == {"num_hidden_layers": 40}
    d = cfg["deployment"]
    assert (d["pipeline_stages"], d["stage"], d["layers_a_stage"]) == (8, 0, 5)
    assert "distorts" in d
    assert set(cfg["assumed"]) >= {
        "power", "expansion", "gate", "normaliser", "scale",
        "qk_norm_rotation", "recurrence_dtype", "chunk_size", "weights_init"}
    fam = family()
    assert fam.kv_layout(cfg) == (1, 8, 128)
    from deepspeed_tpu.models.brumby import BrumbyConfig
    model = fam.build_model(cfg, jnp.bfloat16)
    whole = BrumbyConfig.brumby_14b_base()
    for key in fam.MODEL_KEYS:
        assert getattr(whole, key) == published[key]
        if key != "num_hidden_layers":
            assert getattr(model.config, key) == published[key]
    assert model.config.num_hidden_layers == 5
    assert (model.config.chunk_size, model.config.retention_eps) == (128, 1e-6)
    assert cfg["engine"]["serving"]["preemption"] == "none"


def test_the_memory_account_is_its_arithmetic():
    """The file's numbers recomputed from its widths; the engine is held to
    the slot's bytes on the chip (``check_engine``)."""
    cfg = Registry().config(CONFIG)
    n = cfg["memory_account_numbers"]
    H, V, L, F = (cfg["hidden_size"], cfg["vocab_size"],
                  cfg["num_hidden_layers"], cfg["intermediate_size"])
    Hq, Hk, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    layer = 2 * H * Hq * d + 2 * H * Hk * d + H * Hk + Hk + 2 * d \
        + 3 * H * F + 2 * H
    assert layer == 330352904
    params = L * layer + 2 * V * H + H
    assert n["weight_bytes"] == 2 * params == 6415188560
    state = family().state_layout(cfg)
    assert (state["d_state"], state["d_inner"], state["d_conv"]) == (
        1032, 8320, 1)
    assert n["state_bytes_a_sequence"] == state["bytes_per_sequence"] \
        == L * 4 * 1032 * 8320 == L * pr_work.state_bytes(Hk, d) \
        + L * 4 * (1032 * 8320 - Hk * 129 * 8256)
    sm = cfg["engine"]["state_manager"]
    assert n["state_slots"] == sm["max_tracked_sequences"] + 1 == 37
    assert n["state_pool_bytes"] == 37 * n["state_bytes_a_sequence"]
    assert n["scratch_page_bytes"] == 2 * Hk * 128 * d * 2
    assert n["free_bytes"] == int(n["hbm_limit_bytes"] * cfg["hbm_fill"]) \
        - n["weight_bytes"] - n["state_pool_bytes"] \
        - cfg["hbm_headroom_bytes"] > 0
    # weights and state alone fill three quarters of the chip
    assert (n["weight_bytes"] + n["state_pool_bytes"]) \
        / n["hbm_limit_bytes"] > 0.75
    assert sm["max_ragged_sequence_count"] == 32
    assert sm["max_ragged_batch_size"] == 32 + 8 * sm["prefill_chunk_size"]
    # the longest request and one decode slice fit the context
    assert 32768 + 1024 + 9 <= sm["max_context"]


# --------------------------------------------------------------------------- #
# what the kernels' roofline shares count
# --------------------------------------------------------------------------- #

PEAKS = {"bf16_flops_per_s": 1e15, "hbm_bytes_per_s": 1e9}
STEP, PASS = "jit_serve_decode_step(1)", "jit_serve_paged_pass(3)"
SCOPES = "jit(serve)/jit(main)/while/body/closed_call/pr/"
#: 4 query heads over 2 KV heads of 8: 36 pairs a head, a state of 2 x 36 x
#: 9 float32 = 2,592 bytes a layer
TINY = {"num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
        "num_hidden_layers": 2, "assumed_numbers": {"chunk_size": 4}}


def test_the_kernels_work_is_counted_from_the_widths():
    w = pr_work.widths(Registry().config(CONFIG))
    assert w == {"heads": 40, "kv_heads": 8, "d": 128, "layers": 5,
                 "chunk": 128}
    assert pr_work.pairs(128) == 8256
    assert pr_work.state_bytes(8, 128) == 8 * 8256 * 129 * 4 == 34080768
    flops, bytes_ = pr_work.decode_call(32, 40, 8, 128)
    # 32 rows x (2 x 32.5 MiB of state + 34 KiB of operands and results)
    assert bytes_ == 32 * (2 * 34080768 + 56 * 128 * 2 + 8 * 4
                           + 40 * 128 * 4)
    assert flops == 32 * 13 * 8 * 8256 * 129 and flops / bytes_ < 2
    per_token = pr_work.scan_token_flops(40, 8, 128, 128)
    assert per_token == 40 * (2 * 128 * 128 + 2 * 8256 * 129) \
        + 8 * 2 * 8256 * 129
    flops, bytes_ = pr_work.scan_call(2048, 8, 40, 8, 128, 128)
    assert flops == 2048 * per_token
    assert bytes_ == 2048 * (64 * 128 * 2 + 40 * 128 * 4 + 64) \
        + 8 * 2 * 34080768
    # by hand at toy widths
    assert pr_work.decode_call(3, 4, 2, 8) == (
        3 * 7.0 * 2 * 36 * 9, 3 * (2.0 * 2592 + 8 * 8 * 2 + 8 + 4 * 8 * 4))
    assert pr_work.scan_call(10, 2, 4, 2, 8, 4) == (
        10.0 * (4 * (2 * 4 * 8 + 2 * 36 * 9) + 2 * 2 * 36 * 9),
        10.0 * (10 * 8 * 2 + 4 * 8 * 4 + 16) + 2 * 2.0 * 2592)


def hand_view(records):
    """One chip. Four executions of the decode step and four of the paged
    pass, a layer loop of two calls each (the step's kernel 1,000 us a call,
    the scan's 400 us); the trace's first and last execution are clipped
    and left out, so three of each are whole. ``records``: ``(name, t0_us,
    t1_us, args)`` on the capture's clock, which runs from 0 to 40 ms."""
    ops, modules = [], []
    for i in range(4):
        t = i * 5e6
        modules.append(Event(STEP, t, 4e6))
        ops += [Event(mosaic("closed_call.21"), t + j * 2e6, 1000e3)
                for j in range(2)]
    for i in range(4):
        t = 20e6 + i * 5e6
        modules.append(Event(PASS, t, 4e6))
        ops += [Event(mosaic("closed_call.7"), t + j * 2e6, 400e3)
                for j in range(2)]
        # the decode rows that ride a pass: not the step's kernel time
        ops.append(Event(mosaic("closed_call.21"), t + 1e6, 50e3))
    kernel = {"closed_call.21": SCOPES + "step/pr_decode_step/pallas_call",
              "closed_call.7": SCOPES + "scan/pr_chunk_scan/pallas_call"}
    capture = Capture(
        trace_path="", start_ns=0.0, stop_ns=40e6,
        records=[("X", n, a * 1e3, b * 1e3, "lane", args, "thread")
                 for n, a, b, args in records])
    return {"trace": Trace(devices={0: DeviceTrace(ops=ops, modules=modules)}),
            "op_names": {STEP: dict(kernel), PASS: dict(kernel)},
            "capture": capture, "peaks": PEAKS, "config": TINY}


def test_the_step_share_by_hand():
    """Whole executions of two 1,000 us calls: 2,000 us a step. Two
    captured steps of 3 and 1 live rows: 2 rows in the mean x 5,448 bytes a
    row a layer x 2 layers = 21,792 bytes, 21.792 us at 1 GB/s."""
    steps = [("serve/decode/step", 100, 200, {"step": 0, "live": 3}),
             ("serve/decode/step", 300, 400, {"step": 1, "live": 1}),
             # began before the capture did: not whole inside it
             ("serve/decode/step", -50, 50, {"step": 9, "live": 900})]
    reader = Registry().reader("pr.step_roofline_share")
    assert reader(hand_view(steps)) == pytest.approx(100 * 21.792 / 2000.0)
    reading = Registry().module("readers", "pr").step_reading(
        hand_view(steps))
    assert (reading["executions"], reading["records"], reading["bound"]) == (
        3, 2, "memory")
    assert Registry().reader("pr.scan_roofline_share")(
        hand_view(steps)) is None


def test_the_scan_share_by_hand():
    """Whole executions of two 400 us calls: 800 us a pass. One captured
    pass of 10 tokens in 2 slots: 4,144 operations a token a layer, 41,440 a
    layer = 41.44 us at the 1 GFLOP/s these peaks give the chip (its bytes,
    13,408 = 13.4 us, are the smaller bound), 82.88 us over two layers."""
    passes = [("serve/prefill/pass", 500, 600,
               {"slots": 2, "tokens": 10, "kind": "paged", "ntok": (7, 3),
                "cached": (10, 0)}),
              ("serve/prefill/pass", 700, 800,
               {"slots": 0, "tokens": 0, "kind": "paged", "ntok": (),
                "cached": ()})]
    view = hand_view(passes)
    view["peaks"] = dict(PEAKS, bf16_flops_per_s=1e9)
    reader = Registry().reader("pr.scan_roofline_share")
    assert reader(view) == pytest.approx(100 * 82.88 / 800.0)
    assert Registry().module("readers", "pr").scan_reading(view)["bound"] \
        == "compute"
    # a capture that holds no pass with a token gives nothing, not 0
    assert reader(hand_view(passes[1:])) is None


def test_the_roofline_readers_say_nothing_where_there_is_nothing():
    """On a program that has no such layer (the parent: another
    configuration's view), in a run without a capture, or on a trace with no
    such call, the readers return nothing and do not raise."""
    readers = Registry().module("readers", "pr")
    view = {"config": Registry().config(CONFIG), "peaks": {}, "trace": None}
    assert readers.step_roofline_share(view) is None
    assert readers.scan_roofline_share(view) is None
    other = {"config": {"hidden_size": 1}, "capture": object(),
             "op_names": {"x": {}}}
    assert readers.step_roofline_share(other) is None
    assert readers.scan_roofline_share(other) is None
    bare = hand_view([("serve/decode/step", 100, 200, {"live": 3})])
    bare["op_names"] = {STEP: {"closed_call.21": "jit(x)/attn/paged_decode"}}
    assert readers.step_roofline_share(bare) is None
