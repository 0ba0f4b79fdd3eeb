"""Build-time capability matrix for the split-K decode ladder.

``AttentionKernelSpec.validate_engine_build`` is THE capability table for
the v2 engine: every (feature x feature) pair the kernel surface cannot
carry refuses there, with one canonical message, at build time.  This
suite walks the split-ladder row of that table — ``attention.decode_splits
> 1`` crossed with sliding window, ALiBi, int8 KV pages, spec decode and
tensor parallelism — and pins the exact refusal text for the single pair
that genuinely cannot compose (split-K x TP: the LSE merge would land
outside the shard_map body).  Everything else on the row must build.

All checks are static: a bare spec namespace + a loaded config, no model,
no devices, no tracing.
"""

import re
from types import SimpleNamespace

import pytest

from deepspeed_tpu.inference.v2.attention import (
    AttentionKernelSpec,
    _SPLIT_TP_MSG,
)
from deepspeed_tpu.inference.v2.config_v2 import (
    AttentionConfig,
    RaggedInferenceEngineConfig,
)


def _spec(window=None, alibi=False, head_dim=128, num_heads=8,
          num_kv_heads=4):
    return SimpleNamespace(head_dim=head_dim, num_heads=num_heads,
                           num_kv_heads=num_kv_heads,
                           window=window, alibi=alibi)


def _cfg(**over):
    return RaggedInferenceEngineConfig.load(dict(over))


LADDER = [1, 2, 4, 8]


# --------------------------------------------------------------------- #
# the one refusal: split-K x tensor parallelism
# --------------------------------------------------------------------- #

class TestSplitTPRefusal:

    @pytest.mark.parametrize("splits", [2, 4, 8])
    @pytest.mark.parametrize("tp", [2, 4])
    def test_split_with_tp_refused_exact_message(self, splits, tp):
        cfg = _cfg(attention={"decode_splits": splits}, tensor_parallel=tp)
        with pytest.raises(NotImplementedError,
                           match=re.escape(_SPLIT_TP_MSG)):
            AttentionKernelSpec.validate_engine_build(_spec(), cfg)

    def test_message_text_pinned(self):
        # the canonical text is an API surface (callers catch on it) — pin
        # it verbatim so a reword shows up as a deliberate diff here.
        assert _SPLIT_TP_MSG == (
            "attention.decode_splits > 1 with tensor_parallel > 1 is "
            "not wired (the split-K LSE merge would land outside the "
            "shard_map body)")

    @pytest.mark.parametrize("tp", [2, 4])
    def test_split_one_with_tp_composes(self, tp):
        # split=1 keeps the chunk-serial kernels exactly; TP stays legal.
        cfg = _cfg(attention={"decode_splits": 1}, tensor_parallel=tp)
        AttentionKernelSpec.validate_engine_build(_spec(), cfg)

    def test_kv_quant_tp_refusal_takes_precedence(self):
        # int8 KV x TP refuses first (its row of the table is checked
        # before the split row) — the split-K message must not shadow it.
        cfg = _cfg(attention={"decode_splits": 4}, tensor_parallel=2,
                   kv_quant={"enabled": True})
        with pytest.raises(NotImplementedError,
                           match="kv_quant with tensor_parallel"):
            AttentionKernelSpec.validate_engine_build(_spec(), cfg)


# --------------------------------------------------------------------- #
# tensor parallelism that does not divide the heads is refused, not
# quietly served at tp=1
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("heads, kv_heads, tp", [
    (8, 2, 4),      # kv heads do not divide
    (6, 6, 4),      # neither divides
    (9, 3, 2),
])
def test_tp_not_dividing_heads_refused(heads, kv_heads, tp):
    cfg = _cfg(tensor_parallel=tp)
    with pytest.raises(ValueError, match="does not fall back to tp=1"):
        AttentionKernelSpec.validate_engine_build(
            _spec(num_heads=heads, num_kv_heads=kv_heads), cfg)


# --------------------------------------------------------------------- #
# everything else on the row composes
# --------------------------------------------------------------------- #

class TestSplitComposition:

    @pytest.mark.parametrize("splits", LADDER)
    def test_plain_ladder_composes(self, splits):
        cfg = _cfg(attention={"decode_splits": splits})
        AttentionKernelSpec.validate_engine_build(_spec(), cfg)

    @pytest.mark.parametrize("splits", LADDER)
    def test_sliding_window_composes(self, splits):
        # the window mask is applied inside each split before the LSE
        # merge; fully-masked splits contribute zero weight.
        cfg = _cfg(attention={"decode_splits": splits})
        AttentionKernelSpec.validate_engine_build(_spec(window=64), cfg)

    @pytest.mark.parametrize("splits", LADDER)
    def test_alibi_composes(self, splits):
        cfg = _cfg(attention={"decode_splits": splits})
        AttentionKernelSpec.validate_engine_build(_spec(alibi=True), cfg)

    @pytest.mark.parametrize("splits", LADDER)
    def test_int8_kv_composes(self, splits):
        # per-page dequant happens inside each split's gather, so the
        # merge sees f32 partials either way.
        cfg = _cfg(attention={"decode_splits": splits},
                   kv_quant={"enabled": True})
        AttentionKernelSpec.validate_engine_build(
            _spec(head_dim=128, num_kv_heads=2), cfg)

    @pytest.mark.parametrize("splits", LADDER)
    def test_spec_decode_composes(self, splits):
        # verify steps ride the chunk dispatcher, which carries the same
        # split ladder.
        cfg = _cfg(attention={"decode_splits": splits},
                   spec_decode={"enabled": True, "k": 2})
        AttentionKernelSpec.validate_engine_build(_spec(), cfg)

    @pytest.mark.parametrize("splits", LADDER)
    def test_window_alibi_int8_stack_composes(self, splits):
        cfg = _cfg(attention={"decode_splits": splits},
                   kv_quant={"enabled": True})
        AttentionKernelSpec.validate_engine_build(
            _spec(window=64, alibi=True), cfg)

    @pytest.mark.parametrize("splits", [2, 8])
    def test_orthogonal_window_refusals_survive(self, splits):
        # split-K does not unlock pairs refused elsewhere in the table:
        # spec_decode x window still refuses with its own message.
        cfg = _cfg(attention={"decode_splits": splits},
                   spec_decode={"enabled": True, "k": 2})
        with pytest.raises(NotImplementedError, match="sliding-window"):
            AttentionKernelSpec.validate_engine_build(_spec(window=32), cfg)


# --------------------------------------------------------------------- #
# config-level knob validation
# --------------------------------------------------------------------- #

class TestAttentionConfig:

    @pytest.mark.parametrize("bad", [0, -1, 3, 6, 12])
    def test_non_pow2_splits_rejected(self, bad):
        with pytest.raises(ValueError, match="power of two"):
            AttentionConfig(decode_splits=bad)

    @pytest.mark.parametrize("ok", [1, 2, 4, 8, 16])
    def test_pow2_splits_accepted(self, ok):
        assert AttentionConfig(decode_splits=ok).decode_splits == ok

    def test_min_ctx_per_split_floor(self):
        with pytest.raises(ValueError, match="min_ctx_per_split"):
            AttentionConfig(min_ctx_per_split=0)

    def test_load_round_trip(self):
        cfg = _cfg(attention={"decode_splits": 4, "min_ctx_per_split": 64})
        assert cfg.attention.decode_splits == 4
        assert cfg.attention.min_ctx_per_split == 64

    def test_default_is_split_one(self):
        assert _cfg().attention.decode_splits == 1
