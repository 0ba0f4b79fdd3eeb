"""The plain reference for SDAR (``chipbench/reference/sdar_ref.py``) against
a few lines of numpy that write its equations out; the zoo's module against
it; the block rule, the margin, the controls and the generation loop each
shown to do what the check leans on; and the cell the configuration runs in:
its files, its arithmetic, its traffic, its metrics' readers. What this file
says of ``BENCHMARK.json`` it says by membership, not by place: a later cell
moves nothing here."""

import json
import os
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.harness import Registry  # noqa: E402
from chipbench.reference import sdar_ref as ref  # noqa: E402

CELL = "sdar-30b-a3b-serve-pp8.blockgen-closed-128"
CONFIG = "sdar-30b-a3b-serve-pp8"
TRAFFIC = "blockgen-closed-128"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
OWN = ("block_step_ms.blockgen", "block_tokens_per_row_pass.blockgen",
       "block_commit_share.blockgen", "block_attend_share.blockgen",
       "block_attend_roofline_share.blockgen")
CHUNK = "paged_chunk_roofline_share.blockgen"
B = 4


def family():
    return Registry().module("families", "sdar")


@pytest.fixture(scope="module")
def model():
    """The tiny preset, every norm's gain moved off one, as (module, params,
    configuration-file keys, reference weights, hp)."""
    from deepspeed_tpu.models.sdar import SdarMoeConfig, SdarMoeForCausalLM
    cfg = SdarMoeConfig.tiny(dtype=jnp.float32)
    module = SdarMoeForCausalLM(cfg)
    params = module.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 8), jnp.int32))["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 100))

    def shake(path, leaf):
        if any("norm" in getattr(p, "key", "") for p in path):
            return leaf + 0.2 * jax.random.normal(next(keys), leaf.shape)
        return leaf

    params = jax.tree_util.tree_map_with_path(shake, params)
    d = {k: getattr(cfg, k) for k in family().MODEL_KEYS}
    return (module, params, d, family().reference_weights(params, d),
            family().reference_hp(d))


def ids_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, 200, size=n).astype(
        np.int32)


def close(got, want, tol=1e-4):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol,
                               rtol=tol)


# --------------------------------------------------------------------------- #
# the equations
# --------------------------------------------------------------------------- #

def test_attention_is_causal_by_block():
    rng = np.random.default_rng(0)
    q, k, v = (rng.standard_normal(s).astype(np.float32)
               for s in ((11, 4, 8), (11, 2, 8), (11, 2, 8)))
    got = np.asarray(ref.attention(jnp.asarray(q), jnp.asarray(k),
                                   jnp.asarray(v), B))
    for t in range(11):
        seen = [s for s in range(11) if s // B <= t // B]
        for h in range(4):
            sc = np.array([q[t, h] @ k[s, h // 2] for s in seen]) / 8 ** 0.5
            p = np.exp(sc - sc.max())
            want = (p / p.sum()) @ np.stack([v[s, h // 2] for s in seen])
            close(got[t, h], want, 1e-5)
    causal = np.asarray(ref.attention(jnp.asarray(q), jnp.asarray(k),
                                      jnp.asarray(v), B, causal=True))
    # a block's last row sees the same keys either way; its first does not
    close(causal[3], got[3], 1e-6)
    assert np.abs(causal[4] - got[4]).max() > 1e-2


def test_the_router_weighs_the_chosen_over_their_own_sum():
    rng = np.random.default_rng(1)
    h = rng.standard_normal((5, 16)).astype(np.float32)
    router = rng.standard_normal((16, 8)).astype(np.float32)
    dense, margin = (np.asarray(x) for x in ref.route(
        jnp.asarray(h), jnp.asarray(router), 2))
    logits = h @ router
    for t in range(5):
        p = np.exp(logits[t] - logits[t].max())
        p /= p.sum()
        top = np.argsort(-logits[t])
        want = np.zeros(8, np.float32)
        want[top[:2]] = p[top[:2]] / p[top[:2]].sum()
        close(dense[t], want, 1e-5)
        close(margin[t], logits[t][top[1]] - logits[t][top[2]], 1e-5)


def test_the_zoo_module_is_the_reference(model):
    module, params, _, weights, hp = model
    ids = ids_of(23)
    want = module.apply({"params": params}, jnp.asarray(ids)[None])[0]
    got = ref.forward_logits(weights, ids, hp)
    close(got, want, 2e-4)
    rows = [3, 4, 22]
    some, margin = ref.forward_logits(weights, ids, hp, rows=rows,
                                      with_margin=True)
    close(some, np.asarray(got)[rows], 1e-5)
    assert margin.shape == (3,) and float(jnp.min(margin)) >= 0.0


def test_a_later_block_is_invisible_and_a_rows_own_block_is_not(model):
    """What the chip's check leans on: a sequence padded with mask tokens to
    a common length gives an earlier block's rows the same logits, and a row
    sees its own block's LATER rows."""
    _, _, _, weights, hp = model
    ids = ids_of(16)
    m = hp["mask_token_id"]
    short = ref.forward_logits(weights, ids[:12], hp, rows=[8, 9, 10, 11])
    padded = ref.forward_logits(
        weights, np.concatenate([ids[:12], [m] * 8]).astype(np.int32), hp,
        rows=[8, 9, 10, 11])
    close(padded, short, 1e-5)
    other = ids[:12].copy()
    other[11] = (other[11] + 1) % 200
    moved = ref.forward_logits(weights, other, hp, rows=[8, 7])
    assert np.abs(np.asarray(moved[0]) - np.asarray(short[0])).max() > 1e-3
    base = ref.forward_logits(weights, ids[:12], hp, rows=[7])
    close(moved[1], base[0], 1e-5)       # the block before does not see it


def test_the_controls_read_over_the_float32_run(model):
    _, _, _, weights, hp = model
    ids = ids_of(24, seed=3)
    rows = np.arange(16, 24)
    runs = [dict(ids=ids, rows=rows),
            dict(ids=ids, rows=rows, act_dtype=jnp.bfloat16),
            dict(ids=ids, rows=rows, act_dtype=jnp.float8_e4m3fn),
            dict(ids=ids, rows=rows, causal=True)]
    (f32, _), (bf16, _), (f8, _), (causal, _) = ref.forward_many(weights,
                                                                 runs, hp)
    err = lambda x: float(np.max(np.abs(np.asarray(x) - np.asarray(f32)))
                          / np.max(np.abs(np.asarray(f32))))
    assert 0.0 < err(bf16) < err(f8)
    assert err(f8) > 3e-2
    # rows 19 and 23 close their blocks: the causal mask hides nothing of a
    # block from them — but their context's rows were masked otherwise
    assert err(causal) > 1e-2
    again = ref.forward_logits(weights, ids, hp, rows=rows)
    assert np.array_equal(np.asarray(again), np.asarray(f32))


def test_num_transfer_tokens():
    assert ref.num_transfer_tokens(4, 2) == [2, 2]
    assert ref.num_transfer_tokens(4, 4) == [1, 1, 1, 1]
    assert ref.num_transfer_tokens(8, 3) == [3, 3, 2]
    assert ref.num_transfer_tokens(4, 3) == [2, 1, 1]


def test_denoise_choice_takes_the_most_confident_and_breaks_ties_low():
    m, V = 9, 10
    logits = np.zeros((4, V), np.float32)
    logits[0, 3] = 5.0      # unmasked: never taken
    logits[1, 4] = 2.0
    logits[2, 5] = 2.0      # ties with position 1
    logits[3, m] = 9.0      # the mask token is never chosen
    logits[3, 6] = 1.0
    block = [7, m, m, m]
    new, x0, conf, took = ref.denoise_choice(logits, block, 1,
                                             {"mask_token_id": m})
    assert took == [1] and new.tolist() == [7, 4, m, m]
    assert x0.tolist() == [3, 4, 5, 6] and conf[0] == 0.0
    assert conf[1] == conf[2] > conf[3] > 0.0
    new, _, _, took = ref.denoise_choice(logits, block, 2,
                                         {"mask_token_id": m})
    assert took == [1, 2]
    # the dynamic rule: everything over the threshold where that is enough
    rule = {"mask_token_id": m, "threshold": float(conf[3]) - 1e-3}
    assert ref.denoise_choice(logits, block, 1, rule)[3] == [1, 2, 3]
    rule["threshold"] = float(conf[1]) + 1e-3          # nothing passes
    assert ref.denoise_choice(logits, block, 1, rule)[3] == [1]
    assert ref.denoise_choice(logits, block, 5, rule)[3] == [1, 2, 3]


def test_the_choices_controls_rank_the_wrong_way():
    """``rule["order"]``: what a check puts in the program's place to see
    its comparison fail."""
    m, V = 9, 10
    logits = np.zeros((4, V), np.float32)
    for i, top in enumerate((4.0, 1.0, 3.0, 2.0)):
        logits[i, i] = top
    block = [m, m, m, m]
    took = lambda **rule: ref.denoise_choice(
        logits, block, 2, dict(rule, mask_token_id=m))[3]
    assert took() == [0, 2]
    assert took(order="least") == [1, 3]
    assert took(order="position") == [0, 1]


def _hand_passes(after_of, m=9, V=10, blocks=6, seed=0):
    """Denoise passes of a block of four masks, two positions a pass, for
    ``choice_check``: the program's logits drawn, the reference's equal to
    them, ``after_of(logits, ids, n_take)`` the block the program left."""
    rng = np.random.default_rng(seed)
    passes, got_of = [], {"ref": {}, "low": {}}
    for b in range(blocks):
        ids = np.full((4,), m, np.int32)
        for n_take in (2, 2):
            logits = rng.standard_normal((4, V)).astype(np.float32)
            after = np.asarray(after_of(logits, ids, n_take), np.int32)
            n = len(passes)
            passes.append((1, 4 * b, ids, n_take, after, logits))
            got_of["ref"][n] = got_of["low"][n] = (logits, None, 0)
            ids = after
    return passes, got_of


@pytest.mark.parametrize("order, failed", [
    (None, []), ("least", ["the choice"]), ("position", ["the choice"])])
def test_the_checks_choice_comparison_fails_a_wrong_ranking(order, failed):
    """The driver's own comparison (b): a program that fills the rule's
    positions passes with both controls reading under the limit; one that
    ranks the wrong way round, or by position, is not correct."""
    driver = Registry().driver("serve_closed_blocks").__globals__
    rule = {"mask_token_id": 9}
    passes, got_of = _hand_passes(lambda lg, ids, n: ref.denoise_choice(
        lg, ids, n, dict(rule, order=order))[0])
    lines = []
    ctx = SimpleNamespace(log=lines.append, config={
        "check": {"control_act_dtype": "float8_e4m3fn"}})
    bad = driver["choice_check"](ctx, ref, rule, passes, got_of, 0.75)
    assert [b[:10] for b in bad] == failed
    (held,) = [ln for ln in lines if "had a choice of positions" in ln]
    assert ("6 passes had a choice" in held) and (
        "1.000 of them filled" in held) == (order is None)


@pytest.mark.parametrize("P", [3, 8, 9])
def test_generate_denoises_block_by_block(model, P):
    """The loop itself: blocks of B from the prompt's last whole block on,
    the schedule's entries a pass, the block's own final tokens as context of
    the next, and n tokens whatever the last block holds beyond them."""
    _, _, _, weights, hp = model
    prompt = ids_of(P, seed=P)
    trace = []
    out = ref.generate(weights, prompt, 10, hp, 2, trace=trace)
    assert len(out) == 10 and hp["mask_token_id"] not in out
    lead = P % B
    blocks = -(-(10 + lead) // B)
    starts = sorted({at for at, *_ in trace})
    assert starts == [P - lead + B * i for i in range(blocks)]
    first = [t for t in trace if t[0] == P - lead]
    assert first[0][1][:lead] == prompt[P - lead:].tolist()
    assert first[0][1][lead:] == [hp["mask_token_id"]] * (B - lead)
    assert [len(t[4]) for t in first] == ([2, 2] if lead == 0 else
                                          [2, 1] if lead == 1 else [1])
    # with one step a pass fills the whole block at once: another stream
    once = ref.generate(weights, prompt, 10, hp, 1)
    assert len(once) == 10


# --------------------------------------------------------------------------- #
# the family module and the cell's files
# --------------------------------------------------------------------------- #

def test_the_familys_weights_come_a_layer_at_a_time():
    fam = family()
    cfg = dict(Registry().config(CONFIG), num_hidden_layers=2, vocab_size=512,
               hidden_size=64, moe_intermediate_size=32, num_experts=8,
               num_experts_per_tok=2, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, mask_token_id=511)
    module = fam.build_model(cfg, jnp.float32)
    a = fam.init_params(module, 4100000007, jnp.float32)
    b = fam.init_params(module, 4100000007, jnp.float32)
    c = fam.init_params(module, 5, jnp.float32)
    same = jax.tree_util.tree_map(lambda x, y: bool(jnp.array_equal(x, y)),
                                  a, b)
    assert all(jax.tree_util.tree_leaves(same))
    assert not jnp.array_equal(a["layers_0"]["mlp"]["w_up"],
                               c["layers_0"]["mlp"]["w_up"])
    assert not jnp.array_equal(a["layers_0"]["mlp"]["w_up"],
                               a["layers_1"]["mlp"]["w_up"])
    shapes = jax.eval_shape(lambda k: module.init(
        k, jnp.zeros((1, 8), jnp.int32))["params"], jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda x: x.shape, a) == \
        jax.tree_util.tree_map(lambda x: x.shape, shapes)
    w = fam.reference_weights(a, cfg)
    assert w["lm_head"].shape == (64, 512) and len(w["layers"]) == 2
    assert fam.reference_hp(cfg)["block_length"] == 4


def test_the_registry_finds_the_cell_and_its_files():
    reg = Registry()
    cell = reg.cell(CELL)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert cell["traffic"] == TRAFFIC
    assert cell["driver"] == "serve_closed_blocks"
    assert reg.config(CONFIG)["family"] == "sdar"
    assert callable(reg.driver(cell["driver"]))
    names = {m["name"] for m in reg.metrics_of(CELL, "per_layer")}
    assert set(OWN) | {
        CHUNK, "host_ms_per_step.serve", "decode_rows_mean.serve",
        "compiles_in_window.serve", "device_idle_share.serve",
        "prefill_device_share.serve", "engine_unaccounted_share.serve",
        "kv_pages_peak_share.serve", "moe_ffn_share.serve",
        "attn_full_share.serve", "setup_warmup_s.serve"} <= names
    # no one-token decode step, no row flush, no state, no shared expert
    assert not {"decode_step_ms.serve", "kv_flush_share.serve",
                "paged_decode_roofline_share.serve",
                "state_slots_peak_share.serve",
                "moe_shared_share.serve"} & names
    assert {m["name"] for m in reg.metrics_of(CELL, "end_to_end")} == {
        "serve_tok_s", "setup_s"}
    for name in names:
        assert callable(reg.reader(reg.layer_metric(name)["reader"]))
    assert set(reg.cell(CELL)["layer_notes"]) <= names


def test_the_cell_is_an_entry_of_its_own_on_one_chip():
    cells = [w for w in BENCH["workloads"] if w["config"] == CONFIG]
    assert [w["name"] for w in cells] == [CELL] and cells[0]["chips"] == 1
    (config,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == ["num_hidden_layers"]
    own = [m for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
    # the five of the new layer, and the chunk kernel's share of its roofline
    # in the paged pass (every prompt's pass here) under a name of its own
    assert sorted(m["name"] for m in own) == sorted(OWN + (CHUNK,))
    assert {(m["layer"], m["moves"]) for m in own if m["name"] != CHUNK} == {
        ("block decode", "serve_tok_s")}
    (chunk,) = [m for m in own if m["name"] == CHUNK]
    accepted = Registry().layer_metric("paged_chunk_roofline_share.serve")
    mine = Registry().layer_metric(CHUNK)
    assert all(mine[k] == accepted[k] == chunk.get(k, accepted[k])
               for k in ("layer", "moves", "unit", "better", "source",
                         "reader", "args"))
    (tok_s,) = [m for m in BENCH["end_to_end"] if m["name"] == "serve_tok_s"]
    assert CELL in tok_s["workloads"]


def test_the_traffic_is_the_issues():
    mix = Registry().traffic(TRAFFIC)
    assert (mix["kind"], mix["clients"], mix["pool_requests"], mix["ramp_s"],
            mix["drain_s"], mix["sampling"]) == (
                "serve_closed", 128, 1024, 15.0, 120.0, "greedy")
    assert mix["generation"] == {"block_length": 4, "denoising_steps": 2,
                                 "remasking": "low_confidence_static"}
    # assist-closed.json's length distributions exactly, at four times its
    # clients
    base = Registry().traffic("assist-closed")
    for key in ("prompt_tokens", "output_tokens", "sampling", "warmup"):
        assert mix[key] == base[key]
    assert (base["clients"], base["pool_requests"]) == (32, 256)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 1024,
                                    "sigma": 0.8, "min": 256, "max": 8192}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 256,
                                    "max": 1024}
    # the configuration holds no generation settings: they are the traffic's
    assert "block_decode" not in Registry().config(CONFIG)["engine"]


def test_the_file_holds_the_published_widths():
    cfg = Registry().config(CONFIG)
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    assert {k: cfg[k] for k in published} == published
    assert cfg["num_hidden_layers"] == 6 and cfg["reduced"] == [
        "num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 48}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        rows = [json.loads(line) for line in open(catalog)]
        (row,) = [r for r in rows if r["source_url"] == cfg["source"]]
        for key, value in row["config"].items():
            assert cfg[key] == value or key in cfg["reduced"], key
    d = cfg["deployment"]
    assert (d["pipeline_stages"], d["stage"]) == (8, 0) and d["distorts"]
    assert set(cfg["assumed"]) >= {
        "block_length", "mask_token_id", "qk_norm", "no_logit_shift",
        "tie_rule", "confidence", "partial_first_block", "weights_init"}
    assert (cfg["block_length"], cfg["mask_token_id"]) == (4, 151669)
    assert 0 <= cfg["mask_token_id"] < cfg["vocab_size"]
    fam = family()
    assert fam.kv_layout(cfg) == (6, 4, 128)
    module = fam.build_model(cfg, jnp.bfloat16)
    assert (module.config.num_hidden_layers, module.config.num_experts,
            module.config.block_length) == (6, 128, 4)


def test_the_memory_account_is_its_arithmetic():
    """The file's numbers recomputed from its widths, and the page's bytes
    against the pool's own configuration."""
    from deepspeed_tpu.inference.v2.ragged.kv_cache import KVCacheConfig
    cfg = Registry().config(CONFIG)
    n = cfg["memory_account_numbers"]
    H, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    Hq, Hk, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    E, F = cfg["num_experts"], cfg["moe_intermediate_size"]
    attn = H * (Hq * d + 2 * Hk * d) + Hq * d * H
    layer = E * 3 * H * F + attn + H * E + 2 * H + 2 * d
    assert layer == 623120640
    params = L * layer + 2 * V * H + H
    assert n["weight_bytes"] == 2 * params == 8722111488
    bs = cfg["engine"]["kv_cache"]["block_size"]
    assert n["bytes_a_page"] == bs * L * 2 * Hk * d * 2
    budget = int(n["hbm_limit_bytes"] * cfg["hbm_fill"]) - n["weight_bytes"] \
        - cfg["hbm_headroom_bytes"]
    assert n["page_budget_bytes"] == budget
    pool = KVCacheConfig.from_memory_budget(L, Hk, d, budget, block_size=bs)
    assert pool.bytes_per_block() == n["bytes_a_page"]
    assert n["pages"] == pool.num_blocks == budget // n["bytes_a_page"] \
        == 3447
    assert n["tokens"] == n["pages"] * bs
    sm = cfg["engine"]["state_manager"]
    assert sm["max_ragged_batch_size"] == sm["max_ragged_sequence_count"] \
        + 8 * sm["prefill_chunk_size"]
    # the longest request, a last block's overhang and a run's reservation
    mix = Registry().traffic(TRAFFIC)
    slice_tokens = B * (cfg["engine"]["serving"]["decode_slice"] // 2 + 2)
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] \
        + slice_tokens <= sm["max_context"]
    assert n["weight_bytes"] > 0.25 * n["hbm_limit_bytes"]


def test_block_work_counts_what_the_pool_holds():
    """A token's bytes in ``block_work`` are the page's over its tokens and
    layers, and a pass's floor is its rows' cached context read once."""
    from chipbench.reduce import block_work, kv_work
    from deepspeed_tpu.inference.v2.ragged.kv_cache import KVCacheConfig
    cfg = Registry().config(CONFIG)
    w = kv_work.widths(cfg)
    pool = KVCacheConfig(num_layers=6, num_kv_heads=4, head_dim=128,
                         block_size=128, num_blocks=2, dtype=jnp.bfloat16)
    assert w["token_bytes"] * 128 * 6 == pool.bytes_per_block()
    assert (w["full_layers"], w["windowed_layers"]) == (6, 0)
    ops, bytes_ = block_work.block_call(w, 4, rows=128, ctx_tokens=128 * 1720)
    keys = 128 * 1724
    assert bytes_ == keys * 2048 + 128 * 4 * 2 * 32 * 128 * 2
    assert ops == 4 * keys * 4 * 32 * 128
    assert ops / bytes_ < 240            # a v5e's ridge: bytes bind


def test_the_block_readers_read_a_capture_and_nothing_without_one():
    reg = Registry()
    tokens = reg.reader("blocks.tokens_per_row_pass")
    share = reg.reader("blocks.commit_share")
    roof = reg.reader("blocks.attend_roofline_share")
    from deepspeed_tpu.monitor.trace import tracer
    assert roof({}) is None
    # a program that has counted no row-pass: 0.0, not nothing (a counter
    # may not be absent), with or without a capture
    kept = {k: tracer.totals.pop(k) for k in list(tracer.totals)
            if k.startswith("serve/block/")}
    try:
        old = {"capture": SimpleNamespace(counters={"compile/x": 1.0})}
        assert tokens({}) == share({}) == tokens(old) == share(old) == 0.0
        tracer.bump("serve/block/row_passes", 30.0)
        tracer.bump("serve/block/tokens_committed", 36.0)
        assert tokens({}) == tokens(old) == pytest.approx(1.2)
    finally:
        for k in ("serve/block/row_passes", "serve/block/tokens_committed"):
            tracer.totals.pop(k, None)
        tracer.totals.update(kept)
    view = {"capture": SimpleNamespace(counters={
        "serve/block/row_passes": 300.0, "serve/block/tokens_committed":
        396.0, "serve/block/commit_row_passes": 100.0})}
    assert tokens(view) == pytest.approx(1.32)
    assert share(view) == pytest.approx(100.0 / 3)
    for name, unit in (("block_tokens_per_row_pass.blockgen", "tokens"),
                       ("block_attend_roofline_share.blockgen", "%")):
        spec = reg.layer_metric(name)
        assert spec["unit"] == unit and spec["layer"] == "block decode"


def test_the_scope_patterns_find_the_block_steps_attention():
    import re
    from chipbench.reduce import hlo_names
    blocks = Registry().module("readers", "blocks")
    step = ("jit(serve_block_step)/block_step/while/body/attn/attn_full/"
            "paged_chunk/pallas_call")
    write = ("jit(serve_block_step)/block_step/while/body/attn/attn_full/"
             "scatter")
    other = "jit(serve_paged_pass)/while/body/attn/attn_full/paged_chunk"
    head = "jit(serve_block_step)/block_step/denoise/argmax"
    kernel = hlo_names.scope_pattern(blocks.SCOPE)
    assert kernel.search(step) and not kernel.search(write)
    assert not kernel.search(other) and not kernel.search(head)
    scope = Registry().layer_metric("block_attend_share.blockgen")["args"][
        "scope"]
    share = hlo_names.scope_pattern(scope)
    assert share.search(step) and share.search(write)
    assert not share.search(other) and not share.search(head)
    assert re.compile(blocks.SCOPE)
