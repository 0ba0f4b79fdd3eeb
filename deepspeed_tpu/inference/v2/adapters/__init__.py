"""Weight adapters: how a family's published weights become
``(RaggedModelSpec, stacked weights)``, one module a family or lineage.

An adapter runs once, eagerly, on the model's parameter tree; it traces
nothing and imports neither the program builders nor the engine. To serve a
new family: ``models/<family>.py``, ``adapters/<family>.py``, one line in
:data:`ADAPTERS`.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

from deepspeed_tpu.inference.v2.adapters.afmoe import adapt_afmoe
from deepspeed_tpu.inference.v2.adapters.brumby import adapt_brumby
from deepspeed_tpu.inference.v2.adapters.decoder import adapt_decoder
from deepspeed_tpu.inference.v2.adapters.gpt2 import adapt_gpt2
from deepspeed_tpu.inference.v2.adapters.granite import adapt_granite
from deepspeed_tpu.inference.v2.adapters.jamba import adapt_jamba
from deepspeed_tpu.inference.v2.adapters.joyai import (adapt_glm_dsa,
                                                       adapt_joyai)
from deepspeed_tpu.inference.v2.adapters.llama import adapt_llama
from deepspeed_tpu.inference.v2.adapters.nemotron_h import adapt_nemotron_h
from deepspeed_tpu.inference.v2.adapters.qwen3_next import adapt_qwen3_next
from deepspeed_tpu.inference.v2.adapters.sdar import adapt_sdar
from deepspeed_tpu.inference.v2.adapters.zaya import adapt_zaya
from deepspeed_tpu.inference.v2.model_spec import RaggedModelSpec


ADAPTERS: Dict[str, Callable] = {
    # llama lineage (qwen2 = biased qkv; gemma = structural flags — both are
    # LlamaConfig features the adapter reads)
    "llama": adapt_llama,
    "mistral": adapt_llama,
    "mixtral": adapt_llama,
    "qwen2": adapt_llama,
    "gemma": adapt_llama,
    "gpt2": adapt_gpt2,
    # generic-decoder lineage (canonical param names; re-root + stack)
    "opt": adapt_decoder,
    "falcon": adapt_decoder,
    "phi": adapt_decoder,
    "gpt_neox": adapt_decoder,
    "gptj": adapt_decoder,
    "gpt_bigcode": adapt_decoder,
    "bloom": adapt_decoder,   # ALiBi carried by the paged kernels
    # layers of several kinds in one model (window+rotary / full without
    # positions; dense / MoE), gated attention, sigmoid router, shared expert
    "afmoe": adapt_afmoe,
    # Mamba state-space layers beside a few attention layers: a state pool
    # beside the pages (ragged/state_pool.py)
    "jamba": adapt_jamba,
    # latent attention (MLA): pages of one latent row a token, no head axis
    # (ragged_mla.py); a sigmoid router over experts of which this chip may
    # hold a share
    "joyai": adapt_joyai,
    # the same with a learned selection: an indexer a layer, an index-key
    # pool beside the latent pages, attention over the top-k chosen
    "glm_dsa": adapt_glm_dsa,
    # Mamba-2 (SSD) layers — a matrix state per head in the same pool —
    # beside a few no-position GQA layers, every FFN routed experts (of which
    # this chip may hold a share) plus a shared MLP; four plain multipliers
    "granite": adapt_granite,
    # one block a layer (Mamba-2 with groups of B and C, OR attention, OR
    # two-matrix relu2 experts behind a sigmoid router): BlockKind, and the
    # layer loop scans repeating units of the pattern (layer_units)
    "nemotron_h": adapt_nemotron_h,
    # Gated DeltaNet layers (a delta-rule state in the same pool: DeltaKind,
    # _gdn_mixer) beside gated attention with 256-wide heads, a quarter of
    # each rotated; 512 small experts of which this chip may hold a share,
    # and a shared expert behind a sigmoid gate
    "qwen3_next": adapt_qwen3_next,
    # compressed convolutional attention (pages AND a convolution tail in
    # every layer: CcaKind, _cca_project), a top-1 MLP router whose state
    # goes from layer to layer, a choice that skips the experts, learned
    # scales and biases where a branch joins the stream
    "zaya": adapt_zaya,
    # power retention in every layer (a gated degree-2 linear-attention state
    # and its normaliser in the state pool: PowerKind, _pr_mixer), q and k
    # normed and rotated in front of it; no layer holds pages
    "brumby": adapt_brumby,
    # a plain GQA MoE decoder (q/k norm, 128 small experts, all held) that
    # GENERATES by diffusion over blocks: attention causal by blocks of
    # ``spec.causal_block`` positions, a block of mask tokens denoised in
    # place (build_block_step, blocks/pipeline.py)
    "sdar_moe": adapt_sdar,
}

#: families whose attention needs a bias the ragged kernels don't carry —
#: serve these through the v1 dense engine instead
_UNSUPPORTED = {
    # gpt_neo scores attention WITHOUT the 1/sqrt(head_dim) factor
    # (attn_scale=1.0), which adapt_decoder does not map onto
    # ``spec.attn_scale`` (the paged kernels take one since PR 39). Its alternating
    # global/local layers are no longer what blocks it: the spec carries a
    # kind per layer (``layer_kinds``); adapt_decoder does not map
    # ``attention_layers`` onto them yet
    "gpt_neo": "unscaled attention scores (attn_scale)",
}


def adapt_model(family: str, params: Dict, config,
                max_context: Optional[int] = None) -> Tuple[RaggedModelSpec, Dict]:
    if family in _UNSUPPORTED:
        raise ValueError(
            f"family '{family}' uses {_UNSUPPORTED[family]}, which the ragged "
            "(paged) attention path does not support — serve it through "
            "deepspeed_tpu.init_inference (v1 dense engine) instead")
    if family not in ADAPTERS:
        raise ValueError(f"no ragged adapter for family '{family}' "
                         f"(have {sorted(ADAPTERS)})")
    return ADAPTERS[family](params, config, max_context=max_context)
