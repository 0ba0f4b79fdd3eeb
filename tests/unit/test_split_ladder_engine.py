"""Engine-level flash-decoding split ladder: warmed rungs, zero
steady-state compiles, and rung-invariant token streams.

The engine warms ONE program per pow2 rung ``[1, 2, ..., decode_splits]``
for every hot-path program family (ragged pass, decode step, spec
verify), then picks the rung each step from live context
(``attention.min_ctx_per_split``).  These tests pin the contract at the
engine boundary: the ladder property, the rung selector's pow2-floor
arithmetic, zero compiles across rung swaps after ``warmup()``, stream
equality between the chunk-serial split=1 program and the auto-selected
ladder, and the ``serve/attn`` monitor counters fed from the same stamps
as the trace lane.
"""

import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.pipeline import DecodePipeline


def _params(seed=0):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig(vocab_size=256, hidden_size=512, intermediate_size=256,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=512,
                      dtype=jnp.float32)
    model = LlamaForCausalLM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed),
                                 {"input_ids": jnp.zeros((1, 8), jnp.int32)}
                                 )["params"]
    return model, params


def _engine(model, params, splits=2, min_ctx=16, **extra):
    import jax.numpy as jnp
    econf = {"state_manager": {"max_tracked_sequences": 2,
                               "max_ragged_sequence_count": 2,
                               "max_ragged_batch_size": 64,
                               "prefill_chunk_size": 16, "max_context": 256},
             "kv_cache": {"block_size": 16},
             "attention": {"decode_splits": splits,
                           "min_ctx_per_split": min_ctx},
             "dtype": jnp.float32}
    econf.update(extra)
    return InferenceEngineV2(model=model, model_parameters=params,
                             config=econf)


def _serve(engine, uid, prompt, gen):
    engine._put_nofetch([uid], [np.asarray(prompt, np.int32)])
    out = DecodePipeline(engine, [uid]).run(gen)
    engine.flush([uid])
    return [int(t) for t in out[0]]


PROMPT = list(range(3, 43))  # 40 tokens: past 2 * min_ctx -> rung 2


@pytest.fixture(scope="module")
def ladder_engine():
    model, params = _params()
    e = _engine(model, params, splits=2, min_ctx=16)
    e.warmup()
    return e


def test_ladder_property():
    # pure config arithmetic — evaluate the property against a config stub
    # instead of paying four engine builds
    from types import SimpleNamespace
    from deepspeed_tpu.inference.v2.config_v2 import AttentionConfig
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2 as E
    for top, want in [(1, [1]), (2, [1, 2]), (4, [1, 2, 4]),
                      (8, [1, 2, 4, 8])]:
        stub = SimpleNamespace(config=SimpleNamespace(
            attention=AttentionConfig(decode_splits=top)))
        assert E.attn_split_ladder.fget(stub) == want


def test_rung_selector_pow2_floor(ladder_engine):
    e = ladder_engine
    # no live sequences -> shortest program
    assert e._attn_rung() == 1
    # override clamps into the ladder
    e.attn_rung_override = 2
    assert e._attn_rung() == 2
    e.attn_rung_override = 64
    assert e._attn_rung() == 2          # clamped to top rung
    e.attn_rung_override = None


def test_zero_steady_state_compiles_across_rung_swaps(ladder_engine):
    e = ladder_engine
    c0 = e.compiles
    # auto selection: short ctx starts at rung 1, climbs to rung 2 as the
    # 40-token prompt lands — both programs came out of warmup.
    _serve(e, 0, PROMPT, 6)
    assert e.compiles == c0, "rung swap compiled on the hot path"
    # forced split=1 and forced top rung: still warm
    e.attn_rung_override = 1
    _serve(e, 1, PROMPT, 6)
    e.attn_rung_override = 2
    _serve(e, 2, PROMPT, 6)
    e.attn_rung_override = None
    assert e.compiles == c0


def test_stream_invariant_across_rungs(ladder_engine):
    e = ladder_engine
    e.attn_rung_override = 1            # chunk-serial baseline
    ref = _serve(e, 0, PROMPT, 8)
    e.attn_rung_override = None         # auto ladder (reaches rung 2)
    got = _serve(e, 1, PROMPT, 8)
    e.attn_rung_override = 2            # forced top rung
    forced = _serve(e, 2, PROMPT, 8)
    e.attn_rung_override = None
    assert got == ref
    assert forced == ref


def test_attn_stats_counters(ladder_engine):
    e = ladder_engine
    e.attn_stats.reset()
    _serve(e, 0, PROMPT, 6)
    s = e.attn_stats
    assert s.selects > 0
    assert s.splits >= s.selects        # every select contributes >= rung 1
    assert s.merged_steps > 0           # the 40-token ctx climbs to rung 2
    assert s.max_live_ctx >= len(PROMPT)
    assert s.splits_per_select >= 1.0
    ev = {name: (st, val) for name, val, st in s.events(step=7)}
    assert ev["serve/attn/selects"] == (7, float(s.selects))
    assert set(ev) == {"serve/attn/selects", "serve/attn/splits_per_select",
                       "serve/attn/merged_steps", "serve/attn/max_live_ctx",
                       "serve/attn/select_ms_per_step"}


def test_allocator_baseline_after_rung_swaps(ladder_engine):
    e = ladder_engine
    free0 = e.free_blocks
    e.attn_rung_override = 1
    _serve(e, 0, PROMPT, 4)
    e.attn_rung_override = None
    _serve(e, 1, PROMPT, 4)
    assert e.free_blocks == free0
