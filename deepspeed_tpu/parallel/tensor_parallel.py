"""Tensor parallelism via sharding rules — the AutoTP analog.

Parity: ``AutoTP`` (reference ``deepspeed/module_inject/auto_tp.py:187``) walks a
torch module graph, finds shardable Linears, and physically slices weights into
``LinearLayer``/``LinearAllreduce`` wrappers (``module_inject/layers.py:16``). On
TPU no weight surgery is needed: a rule maps parameter tree paths to
``PartitionSpec`` entries over the 'tensor' mesh axis, and the SPMD partitioner
derives the column-/row-parallel compute plus the single all-reduce after each
row-parallel matmul — the same comm pattern AutoTP builds by hand. Unlike the
reference (training TP delegated to external Megatron mpu, SURVEY §2.3), TP here
is first-class for training *and* inference.

Rule semantics (regex on '/'-joined param path):
  COLUMN  shard the output dim  (qkv/up projections; reference LinearLayer)
  ROW     shard the input dim   (o/down projections; reference LinearAllreduce)
  VOCAB   shard embedding rows  (vocab-parallel embed)
  REPLICATE keep replicated      (norms, biases of row-parallel layers)
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.comm.mesh import TENSOR_AXIS
from deepspeed_tpu.utils.logging import warning_once

COLUMN = "column"
ROW = "row"
VOCAB = "vocab"
REPLICATE = "replicate"

# (regex, kind) rule tables for the model zoo. Matched against the '/'-joined path.
GPT2_TP_RULES: List[Tuple[str, str]] = [
    (r".*attn/c_attn/kernel", COLUMN),
    (r".*attn/c_proj/kernel", ROW),
    (r".*mlp/c_fc/kernel", COLUMN),
    (r".*mlp/c_proj/kernel", ROW),
    (r".*wte/embedding", VOCAB),
]

LLAMA_TP_RULES: List[Tuple[str, str]] = [
    (r".*(q_proj|k_proj|v_proj)/kernel", COLUMN),
    (r".*o_proj/kernel", ROW),
    (r".*(gate_proj|up_proj)/kernel", COLUMN),
    (r".*down_proj/kernel", ROW),
    (r".*embed_tokens/embedding", VOCAB),
    (r".*lm_head/kernel", COLUMN),
]

BERT_TP_RULES: List[Tuple[str, str]] = [
    (r".*(query|key|value)/kernel", COLUMN),
    (r".*attention_output/kernel", ROW),
    (r".*intermediate/kernel", COLUMN),
    (r".*layer_\d+/output/kernel", ROW),
]

# models/decoder.py DecoderLM canonical names (opt / falcon / phi / gpt_neox)
DECODER_TP_RULES: List[Tuple[str, str]] = [
    (r".*/(wq|wk|wv|bq|bk|bv)", COLUMN),
    (r".*/wo", ROW),
    (r".*mlp/(w_gate|w_up|b_up)", COLUMN),
    (r".*mlp/w_down", ROW),
    (r"embed/embedding", VOCAB),
    (r"lm_head", COLUMN),
]

# canonical *stacked* ragged-model weights (inference/v2/adapters/): layer
# kernels carry a leading [L] (and MoE an [E]) dim, which COLUMN (last dim) / ROW
# (second-to-last) already handle; embeddings/norms/router replicate (no rule)
RAGGED_STACKED_TP_RULES: List[Tuple[str, str]] = [
    (r".*/(wq|wk|wv|wg|bq|bk|bv)", COLUMN),   # wg: afmoe's output gate
    (r".*/wo", ROW),
    (r".*/(w_gate|w_up|b_up)", COLUMN),
    (r".*/w_down", ROW),
    (r"lm_head", COLUMN),
]

MODEL_TP_RULES: Dict[str, List[Tuple[str, str]]] = {
    "gpt2": GPT2_TP_RULES,
    "llama": LLAMA_TP_RULES,
    "mistral": LLAMA_TP_RULES,
    "mixtral": LLAMA_TP_RULES,
    "neox": LLAMA_TP_RULES,
    "bert": BERT_TP_RULES,
    "opt": DECODER_TP_RULES,
    "falcon": DECODER_TP_RULES,
    "phi": DECODER_TP_RULES,
    "gpt_neox": DECODER_TP_RULES,
    "gptj": DECODER_TP_RULES,
    "bloom": DECODER_TP_RULES,
    "gpt_neo": DECODER_TP_RULES,
    "gpt_bigcode": DECODER_TP_RULES,
    "qwen2": LLAMA_TP_RULES,
    "gemma": LLAMA_TP_RULES,
}

# generic fallback patterns for unknown HF-style models (parity: AutoTP's
# tp_parser policy of sharding every Linear it can prove safe)
GENERIC_TP_RULES: List[Tuple[str, str]] = [
    (r".*(q_proj|k_proj|v_proj|query|key|value|c_attn|qkv[^/]*|wi|fc1|c_fc|up_proj|gate_proj|w1|w3)/kernel", COLUMN),
    (r".*(o_proj|out_proj|c_proj|dense_4h_to_h|wo|fc2|down_proj|w2)/kernel", ROW),
]


def _spec_for(kind: str, shape: Sequence[int], tp_size: int) -> Optional[P]:
    """PartitionSpec over the tensor axis for one param; None if not divisible."""
    if kind == REPLICATE or not shape:
        return P()
    if kind == COLUMN:
        dim = len(shape) - 1          # kernels are [in, out] (flax Dense)
    elif kind == ROW:
        dim = max(0, len(shape) - 2)  # [in, out] -> shard in
    elif kind == VOCAB:
        dim = 0
    else:
        raise ValueError(f"unknown tp rule kind {kind}")
    if shape[dim] % tp_size != 0:
        return None
    spec = [None] * len(shape)
    spec[dim] = TENSOR_AXIS
    return P(*spec)


def path_str(path) -> str:
    """'/'-joined parameter tree path (shared by all rule walkers)."""
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def walk_path_rules(params: Any, rules: Sequence[Tuple[str, Any]],
                    spec_fn) -> Any:
    """Map each param leaf through the first matching (regex, kind) rule.

    ``spec_fn(kind, shape, pathstr)`` returns the PartitionSpec (or P() to
    replicate). Shared by TP (this module) and EP (``parallel/moe.py``) spec
    derivation."""
    compiled = [(re.compile(rx), kind) for rx, kind in rules]

    def one(path, leaf):
        pathstr = path_str(path)
        for rx, kind in compiled:
            if rx.fullmatch(pathstr):
                return spec_fn(kind, np.shape(leaf), pathstr)
        return P()

    return jax.tree_util.tree_map_with_path(one, params)


def derive_tp_specs(params: Any, rules: Sequence[Tuple[str, str]],
                    tp_size: int) -> Any:
    """Build a PartitionSpec tree congruent with ``params``.

    Parity: the graph walk of ``AutoTP.tp_parser`` + ``_replace_module`` — here a
    pure function from path to spec. Unmatched or indivisible params replicate.
    """

    def spec_fn(kind, shape, pathstr):
        spec = _spec_for(kind, shape, tp_size)
        if spec is None:
            warning_once(f"TP: '{pathstr}' {shape} not divisible by "
                         f"tp={tp_size}; replicated")
            return P()
        return spec

    return walk_path_rules(params, rules, spec_fn)


def tp_rules_for(model_family: Optional[str]) -> List[Tuple[str, str]]:
    """Look up rules by family name; unknown -> generic AutoTP-style patterns."""
    if model_family is None:
        return GENERIC_TP_RULES
    return MODEL_TP_RULES.get(model_family.lower(), GENERIC_TP_RULES)
