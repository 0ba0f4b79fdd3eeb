"""``decoder_ref.py`` against the program's own models at tiny widths, same
weights: if either drifts, they disagree loudly. Also the CPU rehearsal of a
cell, which prints counts only."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

BASE = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=128, rope_theta=1e4, rms_norm_eps=1e-5)
CASES = {
    "dense": dict(BASE, family="llama", sliding_window=None),
    "windowed": dict(BASE, family="llama", sliding_window=8),
    "top2_routed": dict(BASE, family="mixtral", sliding_window=None,
                        num_local_experts=4, num_experts_per_tok=2),
}


@pytest.mark.parametrize("case", list(CASES))
def test_reference_agrees_with_the_zoo(case):
    import jax
    import jax.numpy as jnp
    from chipbench import models
    from chipbench.reference import decoder_ref

    cfg = CASES[case]
    extra = {"dispatch_mode": "dropless"} if cfg["family"] == "mixtral" else {}
    model = models.build_model(cfg, jnp.float32, **extra)
    params = models.init_params(model, 2**31 + 5, jnp.float32)
    ids = np.random.default_rng(0).integers(0, 256, (2, 24)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = model.apply({"params": params}, ids, method="forward_logits")
    weights = models.reference_weights(params, cfg)
    hp = models.reference_hp(cfg)
    for b in range(2):
        got, margin = decoder_ref.forward_logits(
            weights, jnp.asarray(ids[b]), hp, with_margin=True)
        scale = float(jnp.max(jnp.abs(want[b])))
        assert float(jnp.max(jnp.abs(got - want[b]))) < 1e-4 * scale
        assert bool(jnp.isinf(margin).all()) == (cfg["family"] == "llama")
    rows = jnp.asarray([3, 23])
    picked = decoder_ref.forward_logits(weights, jnp.asarray(ids[0]), hp,
                                        rows=rows)
    assert picked.shape == (2, 256)
    if cfg["family"] == "llama":        # mixtral's loss adds a router term
        loss = model.apply({"params": params},
                           {"input_ids": ids, "labels": ids})
        ref = decoder_ref.next_token_loss(weights, jnp.asarray(ids),
                                          jnp.asarray(ids), hp)
        assert float(loss) == pytest.approx(float(ref), abs=1e-4)


def test_window_changes_the_reference():
    import jax.numpy as jnp
    from chipbench import models
    from chipbench.reference import decoder_ref
    cfg = CASES["dense"]
    model = models.build_model(cfg, jnp.float32)
    params = models.init_params(model, 1, jnp.float32)
    w = models.reference_weights(params, cfg)
    ids = jnp.arange(24) % 256
    full = decoder_ref.forward_logits(w, ids, models.reference_hp(cfg))
    cut = decoder_ref.forward_logits(
        w, ids, models.reference_hp(dict(cfg, sliding_window=8)))
    assert np.allclose(full[:8], cut[:8], atol=1e-5)
    assert not np.allclose(full[8:], cut[8:], atol=1e-3)


def test_rehearsal_prints_counts_only():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w["name"] for w in bench["workloads"] if w["chips"] == 4)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.rehearse", "--workload", cell,
         "--seconds", "1"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = proc.stdout.strip().splitlines()[-1]
    assert "on 4 CPU device(s)" in last and "correct True" in last
    device_metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert not [n for n in device_metrics if n in proc.stdout]
