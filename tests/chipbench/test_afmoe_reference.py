"""``afmoe_ref.py`` against the program's own afmoe model at tiny widths,
same weights; and that the reference notices each thing that sets the
family apart: drop or change one and the logits move by far more than the
tolerance the engine is held to."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

S, F = "sliding_attention", "full_attention"
BASE = dict(family="afmoe", vocab_size=256, hidden_size=64,
            intermediate_size=128, moe_intermediate_size=32,
            num_hidden_layers=4, num_dense_layers=1, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, max_position_embeddings=128,
            rope_theta=1e4, rms_norm_eps=1e-5, sliding_window=8,
            global_attn_every_n_layers=4, layer_types=[S, S, S, F],
            num_experts=8, num_experts_per_tok=2, num_shared_experts=1,
            score_func="sigmoid", route_norm=True, route_scale=2.826,
            mup_enabled=True)
CASES = {
    "three_kinds": BASE,
    "no_norm_no_shared_no_mup": dict(BASE, route_norm=False,
                                     num_shared_experts=0, mup_enabled=False),
    "full_first": dict(BASE, layer_types=[F, S, F, S], num_dense_layers=2),
}
#: what the engine is held to on the chip in bfloat16
#: (chipbench/configs: check.tol_logits of the afmoe configuration)
CHIP_TOL = 0.02


def family():
    from chipbench.harness import Registry
    return Registry().module("families", "afmoe")


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
    from chipbench.reference import afmoe_ref

    cfg = CASES[case]
    model, params, weights, hp = setup(cfg)
    ids = np.random.default_rng(0).integers(0, 256, (2, 24)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = model.apply({"params": params}, ids, method="forward_logits")
    for b in range(2):
        got, margin = afmoe_ref.forward_logits(
            weights, jnp.asarray(ids[b]), hp, with_margin=True)
        scale = float(jnp.max(jnp.abs(want[b])))
        assert float(jnp.max(jnp.abs(got - want[b]))) < 1e-4 * scale
        assert bool(jnp.isfinite(margin).all()) and float(margin.min()) >= 0
    rows = jnp.asarray([3, 23])
    picked, m = afmoe_ref.forward_logits(weights, jnp.asarray(ids[0]), hp,
                                         rows=rows, with_margin=True)
    assert picked.shape == (2, 256) and m.shape == (2,)


def _windowed_full(w, hp):
    return w, dict(hp, windows=[8, 8, 8, 8])


def _rotated_full(w, hp):
    return w, dict(hp, rotary=[True] * 4)


def _bias_weighs(w, hp):
    return w, dict(hp, weigh_with_bias=True)


def _no_route_scale(w, hp):
    return w, dict(hp, route_scale=1.0)


def _without(key):
    def change(w, hp):
        layers = [{k: v for k, v in layer.items() if k != key}
                  for layer in w["layers"]]
        return dict(w, layers=layers), hp
    return change


CHANGES = {
    "full_layer_windowed": _windowed_full,
    "full_layer_rotated": _rotated_full,
    "expert_bias_used_as_weight": _bias_weighs,
    "route_scale_dropped": _no_route_scale,
    "shared_expert_dropped": _without("shared"),
    "attention_gate_dropped": _without("w_attn_gate"),
}


@pytest.mark.parametrize("change", list(CHANGES))
def test_reference_changes_when(change):
    """Each is a way to serve the family wrongly that stays finite and
    plausible; the reference moves by more than the engine's tolerance, so
    an engine doing it fails its check."""
    import jax.numpy as jnp
    from chipbench.reference import afmoe_ref
    _, _, weights, hp = setup(BASE, seed=3)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 256, 40), jnp.int32)
    right = np.asarray(afmoe_ref.forward_logits(weights, ids, hp))
    w2, hp2 = CHANGES[change](weights, hp)
    wrong = np.asarray(afmoe_ref.forward_logits(w2, ids, hp2))
    late = slice(16, None)          # past the window: the kinds differ
    err = np.max(np.abs(wrong[late] - right[late])) / np.max(np.abs(right))
    assert np.isfinite(wrong).all() and err > 2 * CHIP_TOL, err
    if change == "full_layer_windowed":     # inside the window nothing moves
        assert np.allclose(wrong[:8], right[:8], atol=1e-5)


def test_lower_precision_moves_the_reference():
    """The router's scores in bfloat16 change selections; activations handed
    on in float8 miss the chip's tolerance, in bfloat16 they meet it."""
    import jax.numpy as jnp
    from chipbench.reference import afmoe_ref
    _, _, weights, hp = setup(BASE, seed=3)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 256, 40), jnp.int32)
    right, margin = (np.asarray(x) for x in afmoe_ref.forward_logits(
        weights, ids, hp, with_margin=True))
    scale = np.max(np.abs(right))
    clear = margin > 0.02

    def err(**kw):
        got = np.asarray(afmoe_ref.forward_logits(weights, ids, hp, **kw))
        return np.max(np.abs(got - right)[clear]) / scale

    assert err(act_dtype=jnp.bfloat16) < CHIP_TOL
    assert err(act_dtype=jnp.float8_e4m3fn) > CHIP_TOL
    bf16_router = np.asarray(afmoe_ref.forward_logits(
        weights, ids, dict(hp, router_dtype=jnp.bfloat16)))
    assert np.max(np.abs(bf16_router - right)) / scale > 1e-4


def _serving_cells():
    from chipbench.harness import Registry
    reg = Registry()
    return [w["name"] for w in reg.benchmark["workloads"]
            if "prompt_tokens" in reg.traffic(w["traffic"])]


@pytest.mark.parametrize("cell", _serving_cells())
def test_the_longest_request_of_a_mix_fits_the_engine(cell):
    """A seed may deal the longest prompt the most output tokens: prompt,
    output and the one decode slice the frontend funds beyond them
    (``serving.decode_slice`` + 1; ``frontend.check_budget``) have to fit
    ``max_context``, or that seed's run dies at submit."""
    from chipbench.harness import Registry
    from deepspeed_tpu.inference.v2.config_v2 import ServingConfig
    reg = Registry()
    entry = next(w for w in reg.benchmark["workloads"] if w["name"] == cell)
    mix, engine = reg.traffic(entry["traffic"]), reg.config(
        entry["config"])["engine"]
    reserve = engine.get("serving", {}).get(
        "decode_slice", ServingConfig().decode_slice) + 1
    for lengths in (mix, mix["warmup"]):
        assert lengths["prompt_tokens"]["max"] + lengths["output_tokens"][
            "max"] + reserve <= engine["state_manager"]["max_context"]


@pytest.mark.parametrize("prefixes,want", [
    (["jit_serve_prefill_packed", "jit_serve_paged_pass"], 100 * 300 / 900),
    (["jit_serve_paged_pass"], 0.0),
    (["jit_serve_verify"], 0.0)])
def test_prefill_share_of_a_capture_without_a_prefill_pass_is_nought(
        prefixes, want):
    """Two seconds of the long-output closed loop may hold no prefill pass:
    the cell's reader gives 0 there, and nothing only where no operation ran
    at all."""
    from chipbench.harness import Registry
    from chipbench.reduce.xplane import DeviceTrace, Trace
    from tests.chipbench.test_named import hand_trace
    read = Registry().module("readers", "serve_programs").program_share
    assert read({"trace": hand_trace()}, prefixes) == pytest.approx(want)
    empty = Trace(devices={0: DeviceTrace(ops=[], modules=[])})
    assert read({"trace": empty}, prefixes) is None
