"""Per-architecture injection policies: HF transformers -> zoo flax models.

Parity role: the reference's ``module_inject/containers/*.py`` (bert, bloom,
llama, llama2, gptj, gptneox, opt, megatron, ...) — one policy per supported HF
architecture.  Each policy here builds the matching zoo config and converts the
torch ``state_dict`` to the flax param tree (see ``policy.py`` for the transform
conventions: Linear transposes, rotate-half -> interleaved RoPE permutation,
fused-qkv splits).

Covered families: gpt2, bert, llama (1/2/3-style), mistral, mixtral, opt,
falcon, phi, gpt_neox, gptj, bloom.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from deepspeed_tpu.module_inject.policy import (
    HFInjectionPolicy, dense_params, linear_t, ln_params, map_hf_activation,
    register_policy, rope_permute, split_fused_qkv_grouped,
    split_fused_qkv_per_head, to_np)


# --------------------------------------------------------------------------- #
# gpt2                                                                        #
# --------------------------------------------------------------------------- #

@register_policy
class GPT2Policy(HFInjectionPolicy):
    """HF GPT2LMHeadModel -> models.gpt2.GPT2LMHead.  HF GPT-2 uses Conv1D
    ([in, out] weights), so kernels copy over without transpose."""

    model_types = ("gpt2",)

    def build(self, hf_config, dtype):
        from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHead
        cfg = GPT2Config(vocab_size=hf_config.vocab_size,
                         n_positions=hf_config.n_positions,
                         n_embd=hf_config.n_embd, n_layer=hf_config.n_layer,
                         n_head=hf_config.n_head,
                         eps=hf_config.layer_norm_epsilon, dtype=dtype)
        return GPT2LMHead(cfg), cfg

    def convert(self, hf_config, sd) -> Dict[str, Any]:
        def conv1d(prefix):
            return {"kernel": to_np(sd[f"{prefix}.weight"]),
                    "bias": to_np(sd[f"{prefix}.bias"])}

        p: Dict[str, Any] = {
            "wte": {"embedding": to_np(sd["transformer.wte.weight"])},
            "wpe": {"embedding": to_np(sd["transformer.wpe.weight"])},
            "ln_f": ln_params(sd, "transformer.ln_f"),
        }
        for i in range(hf_config.n_layer):
            h = f"transformer.h.{i}"
            p[f"h_{i}"] = {
                "ln_1": ln_params(sd, f"{h}.ln_1"),
                "ln_2": ln_params(sd, f"{h}.ln_2"),
                "attn": {"c_attn": conv1d(f"{h}.attn.c_attn"),
                         "c_proj": conv1d(f"{h}.attn.c_proj")},
                "mlp": {"c_fc": conv1d(f"{h}.mlp.c_fc"),
                        "c_proj": conv1d(f"{h}.mlp.c_proj")},
            }
        return p


# --------------------------------------------------------------------------- #
# bert                                                                        #
# --------------------------------------------------------------------------- #

@register_policy
class BertPolicy(HFInjectionPolicy):
    """HF BertForMaskedLM -> models.bert.BertForMaskedLM (post-LN encoder,
    tied MLM decoder + bias)."""

    model_types = ("bert",)

    def build(self, hf_config, dtype):
        from deepspeed_tpu.models.bert import BertConfig, BertForMaskedLM
        cfg = BertConfig(vocab_size=hf_config.vocab_size,
                         hidden_size=hf_config.hidden_size,
                         num_hidden_layers=hf_config.num_hidden_layers,
                         num_attention_heads=hf_config.num_attention_heads,
                         intermediate_size=hf_config.intermediate_size,
                         max_position_embeddings=hf_config.max_position_embeddings,
                         type_vocab_size=hf_config.type_vocab_size,
                         layer_norm_eps=hf_config.layer_norm_eps,
                         exact_gelu=hf_config.hidden_act == "gelu",
                         mlm_bias=True, dtype=dtype)
        return BertForMaskedLM(cfg), cfg

    def convert(self, hf_config, sd) -> Dict[str, Any]:
        emb = "bert.embeddings"
        p: Dict[str, Any] = {
            "word_embeddings": {"embedding": to_np(sd[f"{emb}.word_embeddings.weight"])},
            "position_embeddings": {"embedding": to_np(sd[f"{emb}.position_embeddings.weight"])},
            "token_type_embeddings": {"embedding": to_np(sd[f"{emb}.token_type_embeddings.weight"])},
            "embeddings_layernorm": ln_params(sd, f"{emb}.LayerNorm"),
            "mlm_transform": dense_params(sd, "cls.predictions.transform.dense"),
            "mlm_layernorm": ln_params(sd, "cls.predictions.transform.LayerNorm"),
            "mlm_bias": to_np(sd["cls.predictions.bias"]),
        }
        for i in range(hf_config.num_hidden_layers):
            l = f"bert.encoder.layer.{i}"
            p[f"layer_{i}"] = {
                "attention": {"query": dense_params(sd, f"{l}.attention.self.query"),
                              "key": dense_params(sd, f"{l}.attention.self.key"),
                              "value": dense_params(sd, f"{l}.attention.self.value")},
                "attention_output": dense_params(sd, f"{l}.attention.output.dense"),
                "attention_layernorm": ln_params(sd, f"{l}.attention.output.LayerNorm"),
                "intermediate": dense_params(sd, f"{l}.intermediate.dense"),
                "output": dense_params(sd, f"{l}.output.dense"),
                "output_layernorm": ln_params(sd, f"{l}.output.LayerNorm"),
            }
        return p


# --------------------------------------------------------------------------- #
# llama / mistral / mixtral                                                   #
# --------------------------------------------------------------------------- #

def _llama_attn(sd, prefix, n_heads, n_kv, head_dim):
    """q/k get the rotate-half -> interleaved permutation; v/o are plain."""
    return {
        "q_proj": {"kernel": rope_permute(linear_t(sd[f"{prefix}.q_proj.weight"]),
                                          n_heads, head_dim)},
        "k_proj": {"kernel": rope_permute(linear_t(sd[f"{prefix}.k_proj.weight"]),
                                          n_kv, head_dim)},
        "v_proj": {"kernel": linear_t(sd[f"{prefix}.v_proj.weight"])},
        "o_proj": {"kernel": linear_t(sd[f"{prefix}.o_proj.weight"])},
    }


class _LlamaBase(HFInjectionPolicy):
    @staticmethod
    def _head_dim(hf_config):
        return getattr(hf_config, "head_dim", None) or \
            hf_config.hidden_size // hf_config.num_attention_heads

    def _cfg_kwargs(self, hf_config):
        return dict(vocab_size=hf_config.vocab_size,
                    hidden_size=hf_config.hidden_size,
                    intermediate_size=hf_config.intermediate_size,
                    num_hidden_layers=hf_config.num_hidden_layers,
                    num_attention_heads=hf_config.num_attention_heads,
                    num_key_value_heads=hf_config.num_key_value_heads,
                    max_position_embeddings=hf_config.max_position_embeddings,
                    rope_theta=getattr(hf_config, "rope_theta", 10000.0),
                    rms_norm_eps=hf_config.rms_norm_eps)

    def convert(self, hf_config, sd) -> Dict[str, Any]:
        hd = self._head_dim(hf_config)
        H, Hkv = hf_config.num_attention_heads, hf_config.num_key_value_heads
        tied = getattr(hf_config, "tie_word_embeddings", False)
        head = sd["model.embed_tokens.weight" if tied else "lm_head.weight"]
        p: Dict[str, Any] = {
            "embed_tokens": {"embedding": to_np(sd["model.embed_tokens.weight"])},
            "norm": {"weight": to_np(sd["model.norm.weight"])},
            "lm_head": {"kernel": linear_t(head)},
        }
        for i in range(hf_config.num_hidden_layers):
            l = f"model.layers.{i}"
            p[f"layers_{i}"] = {
                "input_layernorm": {"weight": to_np(sd[f"{l}.input_layernorm.weight"])},
                "post_attention_layernorm": {
                    "weight": to_np(sd[f"{l}.post_attention_layernorm.weight"])},
                "self_attn": _llama_attn(sd, f"{l}.self_attn", H, Hkv, hd),
                **self._block_extra(hf_config, sd, l),
            }
        return p

    def _block_extra(self, hf_config, sd, l):
        return {"mlp": {
            "gate_proj": {"kernel": linear_t(sd[f"{l}.mlp.gate_proj.weight"])},
            "up_proj": {"kernel": linear_t(sd[f"{l}.mlp.up_proj.weight"])},
            "down_proj": {"kernel": linear_t(sd[f"{l}.mlp.down_proj.weight"])},
        }}


@register_policy
class LlamaPolicy(_LlamaBase):
    """HF LlamaForCausalLM / MistralForCausalLM -> models.llama.LlamaForCausalLM."""

    model_types = ("llama", "mistral")

    def build(self, hf_config, dtype):
        from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        kw = self._cfg_kwargs(hf_config)
        if getattr(hf_config, "sliding_window", None):
            kw["sliding_window"] = hf_config.sliding_window
        cfg = LlamaConfig(dtype=dtype, **kw)
        return LlamaForCausalLM(cfg), cfg


@register_policy
class GemmaPolicy(_LlamaBase):
    """HF GemmaForCausalLM -> models.llama.LlamaForCausalLM with the Gemma
    structural flags: sqrt(hidden)-scaled embeddings, (1 + weight) RMSNorm,
    GeGLU MLP, decoupled head_dim, tied head."""

    model_types = ("gemma",)

    def build(self, hf_config, dtype):
        from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        kw = self._cfg_kwargs(hf_config)
        act = getattr(hf_config, "hidden_activation", None) or hf_config.hidden_act
        cfg = LlamaConfig(head_dim_override=hf_config.head_dim,
                          embed_scale_by_sqrt_dim=True, norm_plus_one=True,
                          mlp_act="gelu" if "gelu" in act else "silu",
                          dtype=dtype, **kw)
        return LlamaForCausalLM(cfg), cfg


@register_policy
class Qwen2Policy(_LlamaBase):
    """HF Qwen2ForCausalLM -> models.llama.LlamaForCausalLM with qkv_bias
    (the Qwen2 lineage is llama + biased q/k/v projections)."""

    model_types = ("qwen2",)

    def build(self, hf_config, dtype):
        from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        kw = self._cfg_kwargs(hf_config)
        if getattr(hf_config, "use_sliding_window", False) and \
                getattr(hf_config, "sliding_window", None):
            kw["sliding_window"] = hf_config.sliding_window
        cfg = LlamaConfig(qkv_bias=True, dtype=dtype, **kw)
        return LlamaForCausalLM(cfg), cfg

    def convert(self, hf_config, sd):
        p = super().convert(hf_config, sd)
        hd = hf_config.hidden_size // hf_config.num_attention_heads
        H, Hkv = hf_config.num_attention_heads, hf_config.num_key_value_heads
        for i in range(hf_config.num_hidden_layers):
            a = f"model.layers.{i}.self_attn"
            attn = p[f"layers_{i}"]["self_attn"]
            attn["q_proj"]["bias"] = rope_permute(
                to_np(sd[f"{a}.q_proj.bias"]), H, hd)
            attn["k_proj"]["bias"] = rope_permute(
                to_np(sd[f"{a}.k_proj.bias"]), Hkv, hd)
            attn["v_proj"]["bias"] = to_np(sd[f"{a}.v_proj.bias"])
        return p


@register_policy
class MixtralPolicy(_LlamaBase):
    """HF MixtralForCausalLM -> models.mixtral.MixtralForCausalLM.  Per-expert
    w1/w3/w2 Linears stack into [E, ...] tensors for the grouped expert FFN."""

    model_types = ("mixtral",)

    def build(self, hf_config, dtype):
        from deepspeed_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM
        cfg = MixtralConfig(num_local_experts=hf_config.num_local_experts,
                            num_experts_per_tok=hf_config.num_experts_per_tok,
                            router_aux_loss_coef=getattr(
                                hf_config, "router_aux_loss_coef", 0.02),
                            dtype=dtype, **self._cfg_kwargs(hf_config))
        return MixtralForCausalLM(cfg), cfg

    def _block_extra(self, hf_config, sd, l):
        E = hf_config.num_local_experts
        moe = f"{l}.block_sparse_moe"
        w_gate = np.stack([linear_t(sd[f"{moe}.experts.{e}.w1.weight"])
                           for e in range(E)])
        w_up = np.stack([linear_t(sd[f"{moe}.experts.{e}.w3.weight"])
                         for e in range(E)])
        w_down = np.stack([linear_t(sd[f"{moe}.experts.{e}.w2.weight"])
                           for e in range(E)])
        return {"block_sparse_moe": {
            "gate": {"kernel": linear_t(sd[f"{moe}.gate.weight"])},
            "w_gate": w_gate, "w_up": w_up, "w_down": w_down,
        }}


@register_policy
class SdarMoePolicy(_LlamaBase):
    """HF ``sdar_moe`` (JetLM SDAR; a Qwen3-MoE body that generates by
    diffusion over blocks) -> models.sdar.SdarMoeForCausalLM. Per-head q/k
    norm gains take the rotate-half -> interleaved permutation their columns
    take; per-expert gate/up/down Linears stack into [E, ...] tensors.
    ``block_length`` and ``mask_token_id`` are ``generate.py``'s arguments,
    not config keys: read from the config where a checkpoint states them,
    else the family's Chat defaults (models/sdar.py)."""

    model_types = ("sdar_moe",)

    def build(self, hf_config, dtype):
        from deepspeed_tpu.models.sdar import SdarMoeConfig, SdarMoeForCausalLM
        kw = self._cfg_kwargs(hf_config)
        for key in ("block_length", "mask_token_id"):
            if getattr(hf_config, key, None) is not None:
                kw[key] = getattr(hf_config, key)
        cfg = SdarMoeConfig(
            head_dim=self._head_dim(hf_config),
            moe_intermediate_size=hf_config.moe_intermediate_size,
            num_experts=hf_config.num_experts,
            num_experts_per_tok=hf_config.num_experts_per_tok,
            norm_topk_prob=hf_config.norm_topk_prob,
            decoder_sparse_step=getattr(hf_config, "decoder_sparse_step", 1),
            mlp_only_layers=tuple(getattr(hf_config, "mlp_only_layers", ())),
            dtype=dtype, **kw)
        return SdarMoeForCausalLM(cfg), cfg

    def _block_extra(self, hf_config, sd, l):
        hd = self._head_dim(hf_config)
        mlp = f"{l}.mlp"
        stack = lambda name: np.stack([
            linear_t(sd[f"{mlp}.experts.{e}.{name}.weight"])
            for e in range(hf_config.num_experts)])
        return {"mlp": {
            "gate": {"kernel": linear_t(sd[f"{mlp}.gate.weight"])},
            "w_gate": stack("gate_proj"), "w_up": stack("up_proj"),
            "w_down": stack("down_proj"),
        }, "_qk_norm": {
            name: {"weight": rope_permute(
                to_np(sd[f"{l}.self_attn.{name}.weight"]), 1, hd)}
            for name in ("q_norm", "k_norm")}}

    def convert(self, hf_config, sd) -> Dict[str, Any]:
        p = super().convert(hf_config, sd)
        for i in range(hf_config.num_hidden_layers):
            layer = p[f"layers_{i}"]
            layer["self_attn"].update(layer.pop("_qk_norm"))
        return p


# --------------------------------------------------------------------------- #
# DecoderLM families: opt / falcon / phi / gpt_neox / gptj / bloom            #
# --------------------------------------------------------------------------- #

class _DecoderBase(HFInjectionPolicy):
    """Shared assembly for the configurable DecoderLM zoo model."""

    def build(self, hf_config, dtype):
        from deepspeed_tpu.models.decoder import DecoderConfig, DecoderLM
        cfg = DecoderConfig(dtype=dtype, **self._decoder_kwargs(hf_config))
        return DecoderLM(cfg), cfg

    def _decoder_kwargs(self, hf_config) -> Dict[str, Any]:
        raise NotImplementedError

    def _assemble(self, embed, layers, final_norm, pos_embed=None,
                  embed_norm=None, lm_head=None, lm_head_bias=None):
        p: Dict[str, Any] = {"embed": {"embedding": embed},
                             "final_norm": final_norm}
        if pos_embed is not None:
            p["pos_embed"] = {"embedding": pos_embed}
        if embed_norm is not None:
            p["embed_norm"] = embed_norm
        if lm_head is not None:
            p["lm_head"] = lm_head
        if lm_head_bias is not None:
            p["lm_head_bias"] = lm_head_bias
        for i, lp in enumerate(layers):
            p[f"layers_{i}"] = lp
        return p

    @staticmethod
    def _attn(wq, wk, wv, wo, bq=None, bk=None, bv=None, bo=None):
        """All inputs in torch [out, in] numpy layout; stores flax [in, out]."""
        d = {"wq": wq.T, "wk": wk.T, "wv": wv.T, "wo": wo.T}
        for k, v in (("bq", bq), ("bk", bk), ("bv", bv), ("bo", bo)):
            if v is not None:
                d[k] = v
        return d

    @staticmethod
    def _mlp(sd, up, down, bias=True):
        m = {"w_up": linear_t(sd[f"{up}.weight"]),
             "w_down": linear_t(sd[f"{down}.weight"])}
        if bias:
            m["b_up"] = to_np(sd[f"{up}.bias"])
            m["b_down"] = to_np(sd[f"{down}.bias"])
        return m


@register_policy
class GPTBigCodePolicy(_DecoderBase):
    """HF GPTBigCodeForCausalLM (StarCoder lineage) -> DecoderLM: GPT-2-style
    learned positions + multi-query attention (1 kv head), tanh GELU."""

    model_types = ("gpt_bigcode",)

    def _decoder_kwargs(self, hf_config):
        n_kv = 1 if hf_config.multi_query else hf_config.n_head
        return dict(family="gpt_bigcode", vocab_size=hf_config.vocab_size,
                    hidden_size=hf_config.n_embd,
                    intermediate_size=hf_config.n_inner or 4 * hf_config.n_embd,
                    num_hidden_layers=hf_config.n_layer,
                    num_attention_heads=hf_config.n_head,
                    num_key_value_heads=n_kv,
                    max_position_embeddings=hf_config.n_positions,
                    activation=map_hf_activation(hf_config.activation_function),
                    learned_pos=True, eps=hf_config.layer_norm_epsilon,
                    tied_lm_head=getattr(hf_config, "tie_word_embeddings", True))

    def convert(self, hf_config, sd) -> Dict[str, Any]:
        from deepspeed_tpu.models.decoder import DecoderConfig
        cfg = DecoderConfig(**self._decoder_kwargs(hf_config))
        hid, D, Hkv = cfg.hidden_size, cfg.head_dim, cfg.kv_heads
        layers = []
        for i in range(hf_config.n_layer):
            l = f"transformer.h.{i}"
            w = to_np(sd[f"{l}.attn.c_attn.weight"])   # [hid + 2*Hkv*D, hid]
            b = to_np(sd[f"{l}.attn.c_attn.bias"])
            if hf_config.multi_query:
                # MQA rows: [q (hid), k (D), v (D)] contiguous
                wq, wk, wv = w[:hid], w[hid:hid + Hkv * D], w[hid + Hkv * D:]
                bq, bk, bv = b[:hid], b[hid:hid + Hkv * D], b[hid + Hkv * D:]
            else:
                # MHA rows interleave per head as [H, 3, D] (NeoX-style)
                wq, wk, wv = split_fused_qkv_per_head(
                    w, hf_config.n_head, D)
                bq, bk, bv = split_fused_qkv_per_head(
                    b, hf_config.n_head, D)
            layers.append({
                "ln1": ln_params(sd, f"{l}.ln_1"),
                "ln2": ln_params(sd, f"{l}.ln_2"),
                **self._attn(wq, wk, wv, to_np(sd[f"{l}.attn.c_proj.weight"]),
                             bq, bk, bv, to_np(sd[f"{l}.attn.c_proj.bias"])),
                "mlp": self._mlp(sd, f"{l}.mlp.c_fc", f"{l}.mlp.c_proj"),
            })
        tied = cfg.tied_lm_head
        return self._assemble(
            to_np(sd["transformer.wte.weight"]), layers,
            ln_params(sd, "transformer.ln_f"),
            pos_embed=to_np(sd["transformer.wpe.weight"]),
            lm_head=None if tied else linear_t(sd["lm_head.weight"]))


@register_policy
class OPTPolicy(_DecoderBase):
    """HF OPTForCausalLM -> DecoderLM(family='opt').  Learned positions with
    the +2 offset baked into the table; tied LM head."""

    model_types = ("opt",)

    def _decoder_kwargs(self, hf_config):
        if getattr(hf_config, "word_embed_proj_dim",
                   hf_config.hidden_size) != hf_config.hidden_size:
            raise ValueError("OPT word_embed_proj_dim != hidden_size (350m "
                             "projection layout) is not supported")
        if not getattr(hf_config, "do_layer_norm_before", True):
            raise ValueError("OPT post-norm (do_layer_norm_before=False) "
                             "is not supported")
        return dict(family="opt", vocab_size=hf_config.vocab_size,
                    hidden_size=hf_config.hidden_size,
                    intermediate_size=hf_config.ffn_dim,
                    num_hidden_layers=hf_config.num_hidden_layers,
                    num_attention_heads=hf_config.num_attention_heads,
                    max_position_embeddings=hf_config.max_position_embeddings,
                    activation=map_hf_activation(hf_config.activation_function),
                    learned_pos=True, pos_offset=2,
                    tied_lm_head=getattr(hf_config, "tie_word_embeddings", True))

    def convert(self, hf_config, sd) -> Dict[str, Any]:
        from deepspeed_tpu.models.decoder import DecoderConfig
        dec = "model.decoder"
        layers = []
        for i in range(hf_config.num_hidden_layers):
            l = f"{dec}.layers.{i}"
            a = f"{l}.self_attn"
            layers.append({
                "ln1": ln_params(sd, f"{l}.self_attn_layer_norm"),
                "ln2": ln_params(sd, f"{l}.final_layer_norm"),
                **self._attn(to_np(sd[f"{a}.q_proj.weight"]),
                             to_np(sd[f"{a}.k_proj.weight"]),
                             to_np(sd[f"{a}.v_proj.weight"]),
                             to_np(sd[f"{a}.out_proj.weight"]),
                             to_np(sd[f"{a}.q_proj.bias"]),
                             to_np(sd[f"{a}.k_proj.bias"]),
                             to_np(sd[f"{a}.v_proj.bias"]),
                             to_np(sd[f"{a}.out_proj.bias"])),
                "mlp": self._mlp(sd, f"{l}.fc1", f"{l}.fc2"),
            })
        cfg = DecoderConfig(**self._decoder_kwargs(hf_config))
        tied = cfg.tied_lm_head
        return self._assemble(
            to_np(sd[f"{dec}.embed_tokens.weight"]), layers,
            ln_params(sd, f"{dec}.final_layer_norm"),
            pos_embed=to_np(sd[f"{dec}.embed_positions.weight"]),
            lm_head=None if tied else linear_t(sd["lm_head.weight"]))


@register_policy
class GPTNeoPolicy(_DecoderBase):
    """HF GPTNeoForCausalLM -> DecoderLM(family='gpt_neo_local').  Learned
    positions, alternating global/local attention layers (window_size), no
    attention-score scaling, bias-free qkv."""

    model_types = ("gpt_neo",)

    @staticmethod
    def _kinds(hf_config):
        kinds = []
        for block, reps in hf_config.attention_types:
            kinds.extend(list(block) * reps)
        return tuple(kinds)

    def _decoder_kwargs(self, hf_config):
        return dict(family="gpt_neo", vocab_size=hf_config.vocab_size,
                    hidden_size=hf_config.hidden_size,
                    intermediate_size=hf_config.intermediate_size
                    or 4 * hf_config.hidden_size,
                    num_hidden_layers=hf_config.num_layers,
                    num_attention_heads=hf_config.num_heads,
                    max_position_embeddings=hf_config.max_position_embeddings,
                    activation=map_hf_activation(hf_config.activation_function),
                    learned_pos=True, attn_scale=1.0,
                    local_window=hf_config.window_size,
                    attention_layers=self._kinds(hf_config),
                    qkv_bias=False, eps=hf_config.layer_norm_epsilon,
                    tied_lm_head=getattr(hf_config, "tie_word_embeddings", True))

    def convert(self, hf_config, sd):
        layers = []
        for i in range(hf_config.num_layers):
            l = f"transformer.h.{i}"
            a = f"{l}.attn.attention"
            layers.append({
                "ln1": ln_params(sd, f"{l}.ln_1"),
                "ln2": ln_params(sd, f"{l}.ln_2"),
                **self._attn(to_np(sd[f"{a}.q_proj.weight"]),
                             to_np(sd[f"{a}.k_proj.weight"]),
                             to_np(sd[f"{a}.v_proj.weight"]),
                             to_np(sd[f"{a}.out_proj.weight"]),
                             bo=to_np(sd[f"{a}.out_proj.bias"])),
                "mlp": self._mlp(sd, f"{l}.mlp.c_fc", f"{l}.mlp.c_proj"),
            })
        tied = getattr(hf_config, "tie_word_embeddings", True)
        return self._assemble(
            to_np(sd["transformer.wte.weight"]), layers,
            ln_params(sd, "transformer.ln_f"),
            pos_embed=to_np(sd["transformer.wpe.weight"]),
            lm_head=None if tied else linear_t(sd["lm_head.weight"]))


@register_policy
class FalconPolicy(_DecoderBase):
    """HF FalconForCausalLM -> DecoderLM(family='falcon').  Handles both the
    7B lineage (multi_query, parallel_attn, single norm) and the 40B "new
    decoder architecture" (grouped kv, ln_attn + ln_mlp dual norms)."""

    model_types = ("falcon",)

    @staticmethod
    def _n_kv(hf_config):
        if hf_config.new_decoder_architecture:
            return hf_config.num_kv_heads
        return 1 if hf_config.multi_query else hf_config.num_attention_heads

    def _decoder_kwargs(self, hf_config):
        if getattr(hf_config, "alibi", False):
            raise ValueError("falcon-rw alibi variants are not supported")
        if not getattr(hf_config, "parallel_attn", True):
            raise ValueError("non-parallel falcon layers are not supported")
        bias = bool(getattr(hf_config, "bias", False))
        return dict(family="falcon", vocab_size=hf_config.vocab_size,
                    hidden_size=hf_config.hidden_size,
                    intermediate_size=getattr(hf_config, "ffn_hidden_size",
                                              4 * hf_config.hidden_size),
                    num_hidden_layers=hf_config.num_hidden_layers,
                    num_attention_heads=hf_config.num_attention_heads,
                    num_key_value_heads=self._n_kv(hf_config),
                    max_position_embeddings=getattr(
                        hf_config, "max_position_embeddings", 2048),
                    activation=map_hf_activation(
                        getattr(hf_config, "activation", "gelu")),
                    rope_theta=getattr(hf_config, "rope_theta", 10000.0),
                    parallel_block=True,
                    parallel_dual_norm=hf_config.new_decoder_architecture,
                    qkv_bias=bias, out_bias=bias, mlp_bias=bias,
                    eps=hf_config.layer_norm_epsilon,
                    tied_lm_head=getattr(hf_config, "tie_word_embeddings", True))

    def convert(self, hf_config, sd) -> Dict[str, Any]:
        from deepspeed_tpu.models.decoder import DecoderConfig
        cfg = DecoderConfig(**self._decoder_kwargs(hf_config))
        H, Hkv, D = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
        layers = []
        for i in range(hf_config.num_hidden_layers):
            l = f"transformer.h.{i}"
            a = f"{l}.self_attention"
            wq, wk, wv = split_fused_qkv_grouped(
                to_np(sd[f"{a}.query_key_value.weight"]), Hkv, H // Hkv, D)
            lp = {
                "ln1": ln_params(sd, f"{l}.ln_attn"
                                 if hf_config.new_decoder_architecture
                                 else f"{l}.input_layernorm"),
                **self._attn(rope_permute(wq.T, H, D).T,
                             rope_permute(wk.T, Hkv, D).T,
                             wv, to_np(sd[f"{a}.dense.weight"])),
                "mlp": self._mlp(sd, f"{l}.mlp.dense_h_to_4h",
                                 f"{l}.mlp.dense_4h_to_h", bias=cfg.mlp_bias),
            }
            if hf_config.new_decoder_architecture:
                lp["ln2"] = ln_params(sd, f"{l}.ln_mlp")
            layers.append(lp)
        tied = cfg.tied_lm_head
        return self._assemble(
            to_np(sd["transformer.word_embeddings.weight"]), layers,
            ln_params(sd, "transformer.ln_f"),
            lm_head=None if tied else linear_t(sd["lm_head.weight"]))


@register_policy
class PhiPolicy(_DecoderBase):
    """HF PhiForCausalLM (phi-1/phi-2 lineage) -> DecoderLM(family='phi').
    Parallel block off one LN, partial rotate-half rotary, biased LM head."""

    model_types = ("phi",)

    def _decoder_kwargs(self, hf_config):
        if getattr(hf_config, "qk_layernorm", False):
            raise ValueError("phi qk_layernorm is not supported")
        return dict(family="phi", vocab_size=hf_config.vocab_size,
                    hidden_size=hf_config.hidden_size,
                    intermediate_size=hf_config.intermediate_size,
                    num_hidden_layers=hf_config.num_hidden_layers,
                    num_attention_heads=hf_config.num_attention_heads,
                    num_key_value_heads=getattr(hf_config, "num_key_value_heads",
                                                None),
                    max_position_embeddings=hf_config.max_position_embeddings,
                    activation=map_hf_activation(hf_config.hidden_act),
                    rope_theta=getattr(hf_config, "rope_theta", 10000.0),
                    rotary_pct=getattr(hf_config, "partial_rotary_factor", 0.5),
                    parallel_block=True, eps=hf_config.layer_norm_eps,
                    head_bias=True,
                    tied_lm_head=getattr(hf_config, "tie_word_embeddings", False))

    def convert(self, hf_config, sd) -> Dict[str, Any]:
        from deepspeed_tpu.models.decoder import DecoderConfig
        cfg = DecoderConfig(**self._decoder_kwargs(hf_config))
        H, Hkv, D, rd = (cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim,
                         cfg.rotary_dim)
        layers = []
        for i in range(hf_config.num_hidden_layers):
            l = f"model.layers.{i}"
            a = f"{l}.self_attn"
            layers.append({
                "ln1": ln_params(sd, f"{l}.input_layernorm"),
                **self._attn(
                    rope_permute(linear_t(sd[f"{a}.q_proj.weight"]), H, D, rd).T,
                    rope_permute(linear_t(sd[f"{a}.k_proj.weight"]), Hkv, D, rd).T,
                    to_np(sd[f"{a}.v_proj.weight"]),
                    to_np(sd[f"{a}.dense.weight"]),
                    rope_permute(to_np(sd[f"{a}.q_proj.bias"]), H, D, rd),
                    rope_permute(to_np(sd[f"{a}.k_proj.bias"]), Hkv, D, rd),
                    to_np(sd[f"{a}.v_proj.bias"]),
                    to_np(sd[f"{a}.dense.bias"])),
                "mlp": self._mlp(sd, f"{l}.mlp.fc1", f"{l}.mlp.fc2"),
            })
        return self._assemble(
            to_np(sd["model.embed_tokens.weight"]), layers,
            ln_params(sd, "model.final_layernorm"),
            lm_head=linear_t(sd["lm_head.weight"]),
            lm_head_bias=to_np(sd["lm_head.bias"]))


@register_policy
class GPTNeoXPolicy(_DecoderBase):
    """HF GPTNeoXForCausalLM -> DecoderLM(family='gpt_neox').  Fused per-head
    qkv, partial rotate-half rotary, dual-norm parallel residual."""

    model_types = ("gpt_neox",)

    def _decoder_kwargs(self, hf_config):
        return dict(family="gpt_neox", vocab_size=hf_config.vocab_size,
                    hidden_size=hf_config.hidden_size,
                    intermediate_size=hf_config.intermediate_size,
                    num_hidden_layers=hf_config.num_hidden_layers,
                    num_attention_heads=hf_config.num_attention_heads,
                    max_position_embeddings=hf_config.max_position_embeddings,
                    activation=map_hf_activation(hf_config.hidden_act),
                    rope_theta=getattr(hf_config, "rope_theta",
                                       getattr(hf_config, "rotary_emb_base",
                                               10000.0)),
                    rotary_pct=hf_config.rotary_pct,
                    parallel_block=hf_config.use_parallel_residual,
                    parallel_dual_norm=hf_config.use_parallel_residual,
                    eps=hf_config.layer_norm_eps,
                    tied_lm_head=getattr(hf_config, "tie_word_embeddings", False))

    def convert(self, hf_config, sd) -> Dict[str, Any]:
        from deepspeed_tpu.models.decoder import DecoderConfig
        cfg = DecoderConfig(**self._decoder_kwargs(hf_config))
        H, D, rd = cfg.num_attention_heads, cfg.head_dim, cfg.rotary_dim
        layers = []
        for i in range(hf_config.num_hidden_layers):
            l = f"gpt_neox.layers.{i}"
            a = f"{l}.attention"
            wq, wk, wv = split_fused_qkv_per_head(
                to_np(sd[f"{a}.query_key_value.weight"]), H, D)
            bq, bk, bv = split_fused_qkv_per_head(
                to_np(sd[f"{a}.query_key_value.bias"]), H, D)
            layers.append({
                "ln1": ln_params(sd, f"{l}.input_layernorm"),
                "ln2": ln_params(sd, f"{l}.post_attention_layernorm"),
                **self._attn(rope_permute(wq.T, H, D, rd).T,
                             rope_permute(wk.T, H, D, rd).T,
                             wv, to_np(sd[f"{a}.dense.weight"]),
                             rope_permute(bq, H, D, rd),
                             rope_permute(bk, H, D, rd),
                             bv, to_np(sd[f"{a}.dense.bias"])),
                "mlp": self._mlp(sd, f"{l}.mlp.dense_h_to_4h",
                                 f"{l}.mlp.dense_4h_to_h"),
            })
        tied = cfg.tied_lm_head
        return self._assemble(
            to_np(sd["gpt_neox.embed_in.weight"]), layers,
            ln_params(sd, "gpt_neox.final_layer_norm"),
            lm_head=None if tied else linear_t(sd["embed_out.weight"]))


@register_policy
class GPTJPolicy(_DecoderBase):
    """HF GPTJForCausalLM -> DecoderLM(family='gptj').  GPT-J's rotary is
    already interleaved (the zoo's native convention) — no permutation."""

    model_types = ("gptj",)

    def _decoder_kwargs(self, hf_config):
        hd = hf_config.n_embd // hf_config.n_head
        return dict(family="gptj", vocab_size=hf_config.vocab_size,
                    hidden_size=hf_config.n_embd,
                    intermediate_size=hf_config.n_inner or 4 * hf_config.n_embd,
                    num_hidden_layers=hf_config.n_layer,
                    num_attention_heads=hf_config.n_head,
                    max_position_embeddings=hf_config.n_positions,
                    activation=map_hf_activation(hf_config.activation_function),
                    rope_theta=10000.0,
                    rotary_pct=(hf_config.rotary_dim or hd) / hd,
                    parallel_block=True, qkv_bias=False, out_bias=False,
                    eps=hf_config.layer_norm_epsilon, head_bias=True,
                    tied_lm_head=getattr(hf_config, "tie_word_embeddings", False))

    def convert(self, hf_config, sd) -> Dict[str, Any]:
        layers = []
        for i in range(hf_config.n_layer):
            l = f"transformer.h.{i}"
            a = f"{l}.attn"
            layers.append({
                "ln1": ln_params(sd, f"{l}.ln_1"),
                **self._attn(to_np(sd[f"{a}.q_proj.weight"]),
                             to_np(sd[f"{a}.k_proj.weight"]),
                             to_np(sd[f"{a}.v_proj.weight"]),
                             to_np(sd[f"{a}.out_proj.weight"])),
                "mlp": self._mlp(sd, f"{l}.mlp.fc_in", f"{l}.mlp.fc_out"),
            })
        return self._assemble(
            to_np(sd["transformer.wte.weight"]), layers,
            ln_params(sd, "transformer.ln_f"),
            lm_head=linear_t(sd["lm_head.weight"]),
            lm_head_bias=to_np(sd["lm_head.bias"]))


@register_policy
class BloomPolicy(_DecoderBase):
    """HF BloomForCausalLM -> DecoderLM(family='bloom').  ALiBi position bias,
    layernorm after the embedding, fused per-head qkv, tied head."""

    model_types = ("bloom",)

    def _decoder_kwargs(self, hf_config):
        return dict(family="bloom", vocab_size=hf_config.vocab_size,
                    hidden_size=hf_config.hidden_size,
                    intermediate_size=4 * hf_config.hidden_size,
                    num_hidden_layers=hf_config.n_layer,
                    num_attention_heads=hf_config.n_head,
                    activation="gelu", alibi=True, embed_norm=True,
                    eps=hf_config.layer_norm_epsilon,
                    tied_lm_head=getattr(hf_config, "tie_word_embeddings", True))

    def convert(self, hf_config, sd) -> Dict[str, Any]:
        from deepspeed_tpu.models.decoder import DecoderConfig
        cfg = DecoderConfig(**self._decoder_kwargs(hf_config))
        H, D = cfg.num_attention_heads, cfg.head_dim
        layers = []
        for i in range(hf_config.n_layer):
            l = f"transformer.h.{i}"
            a = f"{l}.self_attention"
            wq, wk, wv = split_fused_qkv_per_head(
                to_np(sd[f"{a}.query_key_value.weight"]), H, D)
            bq, bk, bv = split_fused_qkv_per_head(
                to_np(sd[f"{a}.query_key_value.bias"]), H, D)
            layers.append({
                "ln1": ln_params(sd, f"{l}.input_layernorm"),
                "ln2": ln_params(sd, f"{l}.post_attention_layernorm"),
                **self._attn(wq, wk, wv, to_np(sd[f"{a}.dense.weight"]),
                             bq, bk, bv, to_np(sd[f"{a}.dense.bias"])),
                "mlp": self._mlp(sd, f"{l}.mlp.dense_h_to_4h",
                                 f"{l}.mlp.dense_4h_to_h"),
            })
        tied = cfg.tied_lm_head
        return self._assemble(
            to_np(sd["transformer.word_embeddings.weight"]), layers,
            ln_params(sd, "transformer.ln_f"),
            embed_norm=ln_params(sd, "transformer.word_embeddings_layernorm"),
            lm_head=None if tied else linear_t(sd["lm_head.weight"]))
