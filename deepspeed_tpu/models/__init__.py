"""Model zoo: the BASELINE config ladder families (gpt2, llama/mistral, mixtral,
gpt-neox) plus the inference-container families (opt, falcon, phi, bert) and
afmoe (Arcee Trinity: layers of several kinds in one model), jamba
(state-space layers), joyai (JoyAI-LLM-Flash: latent attention), granite
(IBM Granite 4.0-H: Mamba-2 layers over routed experts), nemotron_h
(Nemotron 3 Nano: one block a layer — Mamba-2, experts or attention) and
qwen3_next (Qwen3-Next: Gated DeltaNet delta-rule layers beside gated
attention, 512 small experts) and zaya (ZAYA1: compressed convolutional
attention, a top-1 MLP router with a state) and brumby (Brumby: power
retention in every layer, no attention over cached keys) and glm_dsa (GLM-5:
latent attention over a learned top-k selection, an indexer a layer) and sdar
(SDAR-30B-A3B: a Qwen3-MoE body that generates by diffusion over blocks,
attention causal by block) — matching the reference's model coverage (module_inject/containers,
inference/v2/model_implementations)."""

from deepspeed_tpu.models.afmoe import AfmoeConfig, AfmoeForCausalLM
from deepspeed_tpu.models.bert import BertConfig, BertForMaskedLM
from deepspeed_tpu.models.brumby import BrumbyConfig, BrumbyForCausalLM
from deepspeed_tpu.models.decoder import (DecoderConfig, DecoderLM,
                                          init_decoder_cache)
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from deepspeed_tpu.models.glm_dsa import GlmDsaConfig, GlmDsaForCausalLM
from deepspeed_tpu.models.granite import GraniteConfig, GraniteForCausalLM
from deepspeed_tpu.models.jamba import JambaConfig, JambaForCausalLM
from deepspeed_tpu.models.joyai import JoyaiConfig, JoyaiForCausalLM
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM, init_cache
from deepspeed_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM
from deepspeed_tpu.models.nemotron_h import (NemotronHConfig,
                                             NemotronHForCausalLM)
from deepspeed_tpu.models.qwen3_next import (Qwen3NextConfig,
                                             Qwen3NextForCausalLM)
from deepspeed_tpu.models.sdar import SdarMoeConfig, SdarMoeForCausalLM
from deepspeed_tpu.models.zaya import ZayaConfig, ZayaForCausalLM
from deepspeed_tpu.models.diffusion import (DiffusionConfig,
                                            DiffusionPipeline,
                                            init_diffusion_inference)
