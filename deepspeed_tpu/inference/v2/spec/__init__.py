"""Speculative decoding for the v2 serving engine — draft-and-verify layered
onto the steady-state decode hot path (docs/SERVING.md "Speculative
decoding").

Decode is memory-bound: every decode
step streams the full model from HBM to emit ONE token per sequence. This
subsystem makes each step pay for up to ``k + 1`` tokens instead:

- ``proposer.py`` — :class:`DraftProposer` (pluggable; a small draft model
  slots in later) with :class:`NGramProposer`, prompt-lookup/n-gram matching
  over each sequence's own token history — no second model, free drafts on
  repetitive/templated text.
- ``pipeline.py`` — :class:`SpecDecodePipeline`: the ``DecodePipeline``
  analog whose step verifies the draft in ONE ragged forward
  (``ragged_model.build_verify_step``: KV written for all k+1 positions,
  greedy accept mask on device, one int32 accept/bonus row per step crossing
  to host) and advances each row by its accepted count — per-step variable
  advance with block-granular rollback of reserved-but-unused pages through
  the refcounted allocator (``scheduler.rollback_reserved``).

Greedy speculation is exactness-preserving: streams are byte-identical to
the spec-off pipeline (``tests/unit/test_spec_decode.py`` holds it), programs
live on the warmed (bucket, k) grid so speculation adds zero timed compiles, and
``monitor/serving.SpecDecodeStats`` + ``serve/spec/*`` trace lanes make the
acceptance economics observable.
"""

from deepspeed_tpu.inference.v2.spec.pipeline import SpecDecodePipeline
from deepspeed_tpu.inference.v2.spec.proposer import (DraftProposer,
                                                      NGramProposer)
