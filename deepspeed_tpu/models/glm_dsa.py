"""GLM-5 (``model_type: glm_moe_dsa``) in flax.linen.

JoyAI's lineage — multi-head latent attention over a sigmoid ``noaux_tc``
router with a shared expert (``models/joyai.py``: this module reuses its MLP,
its MoE layer and its config) — plus what the family is here for: DeepSeek
Sparse Attention, a learned per-token SELECTION inside the latent attention.
Every layer carries an INDEXER: ``index_n_heads`` small heads of
``index_head_dim`` score every earlier token for each query, and attention
runs over the ``index_topk`` best alone.

Layer equations, on the normed input ``h`` of position ``t``
(``chipbench/reference/glm_dsa_ref.py`` states them once more, in float32):

- latent attention as JoyAI's: ``c_q = q_a_layernorm(h W_qa)``, ``q = c_q
  W_qb``, ``[c_kv | k_r] = h W_kva`` ...; ``v_head_dim`` differs from
  ``qk_nope_head_dim`` here;
- the indexer: ``q_idx = c_q W_idx_qb`` -> ``[Hi, Di]``; ``k_idx =
  LayerNorm(h W_idx_k)`` (weight and bias); in both the FIRST
  ``qk_rope_head_dim`` values are rotated by position, pairs ``(2i, 2i+1)``;
  ``w = (h W_idx_w) * Hi ** -0.5 * Di ** -0.5`` in float32;
  ``I[t, s] = sum_j w[t, j] relu(q_idx[t, j] . k_idx[s])`` for ``s <= t``;
- the selection: the ``min(index_topk, t + 1)`` positions of largest
  ``I[t, .]`` (``jax.lax.top_k``: of equal scores the lower position);
- softmax attention over those positions only.

The serving path is ``inference/v2`` through ``adapt_glm_dsa``
(``adapters/joyai.py``) and the kernels of ``ops/pallas/sparse_mla.py``; this
module gives the parameter tree (``init``) and a plain dense forward. Left
out, as the bfloat16 serving path leaves them out: the float8 storage of
index keys and the Hadamard rotation of the published inference code (an
orthogonal map on both sides of a dot product). The multi-token-prediction
module is neither built nor loaded, as for JoyAI.
"""

from __future__ import annotations

from dataclasses import dataclass

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.joyai import JoyaiConfig, JoyaiMLP, JoyaiMoE
from deepspeed_tpu.models.llama import RMSNorm, apply_rope


@dataclass
class GlmDsaConfig(JoyaiConfig):
    """The published ``config.json`` keys under their own names
    (``rope_parameters.rope_theta`` as ``rope_theta``)."""
    vocab_size: int = 154880
    hidden_size: int = 6144
    intermediate_size: int = 12288
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 78
    first_k_dense_replace: int = 3
    num_attention_heads: int = 64
    num_key_value_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    max_position_embeddings: int = 202752
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-5
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    # not in the published config: the index key's LayerNorm
    index_norm_eps: float = 1e-6
    family: str = "glm_dsa"

    def __post_init__(self):
        super().__post_init__()
        if not 0 < self.qk_rope_head_dim <= self.index_head_dim:
            raise ValueError(
                f"the indexer rotates its first {self.qk_rope_head_dim} "
                f"values: index_head_dim {self.index_head_dim} is narrower")

    @classmethod
    def glm_5(cls, **kw):
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw):
        """A dense layer and three MoE layers at toy widths; four index
        heads of 32 (16 rotated) keep the 24 best tokens."""
        d = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                 moe_intermediate_size=32, num_hidden_layers=4,
                 first_k_dense_replace=1, num_attention_heads=4,
                 num_key_value_heads=4, q_lora_rank=48, kv_lora_rank=64,
                 qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=48,
                 max_position_embeddings=512, rope_theta=10000.0,
                 n_routed_experts=16, num_experts_per_tok=4,
                 index_n_heads=4, index_head_dim=32, index_topk=24)
        d.update(kw)
        return cls(**d)


def index_rope(x, positions, cfg: GlmDsaConfig):
    """The indexer's rotation: the first ``qk_rope_head_dim`` values of
    ``x [B, T, H, Di]``."""
    dr = cfg.qk_rope_head_dim
    return jnp.concatenate(
        [apply_rope(x[..., :dr], positions, cfg.rope_theta), x[..., dr:]],
        axis=-1)


class GlmDsaIndexer(nn.Module):
    """Index scores ``[B, T, T]`` float32 (``-inf`` above the diagonal)."""
    config: GlmDsaConfig

    @nn.compact
    def __call__(self, h, cq, positions):
        cfg = self.config
        B, T, _ = h.shape
        Hi, Di = cfg.index_n_heads, cfg.index_head_dim
        dense = lambda feats, name: nn.Dense(feats, use_bias=False,
                                             dtype=cfg.dtype, name=name)
        q = dense(Hi * Di, "wq_b")(cq).reshape(B, T, Hi, Di)
        k = nn.LayerNorm(epsilon=cfg.index_norm_eps, dtype=cfg.dtype,
                         name="k_norm")(dense(Di, "wk")(h))
        q = index_rope(q, positions, cfg)
        k = index_rope(k[:, :, None], positions, cfg)[:, :, 0]
        w = nn.Dense(Hi, use_bias=False, dtype=jnp.float32,
                     name="weights_proj")(h.astype(jnp.float32)) \
            * Hi ** -0.5 * Di ** -0.5
        s = jnp.einsum("bqhd,bkd->bqhk", q, k).astype(jnp.float32)
        scores = jnp.sum(jax.nn.relu(s) * w[..., None], axis=2)
        causal = positions[:, :, None] >= positions[:, None, :]
        return jnp.where(causal, scores, -jnp.inf)


def selection(scores, topk: int):
    """``[B, T, T]`` bool: per query the ``topk`` positions of largest
    score among those it may see (all of them where it sees no more)."""
    T = scores.shape[-1]
    if T <= topk:
        return jnp.isfinite(scores)
    _, idx = jax.lax.top_k(scores, topk)
    picked = jnp.sum(jax.nn.one_hot(idx, T, dtype=jnp.int32), axis=-2) > 0
    return picked & jnp.isfinite(scores)


class GlmDsaAttention(nn.Module):
    """MLA, expanded, over the indexer's selection."""
    config: GlmDsaConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        B, T, _ = x.shape
        H, dn, dr, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
        R = cfg.kv_lora_rank
        dense = lambda feats, name: nn.Dense(feats, use_bias=False,
                                             dtype=cfg.dtype, name=name)
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        cq = norm("q_a_layernorm")(dense(cfg.q_lora_rank, "q_a_proj")(x))
        q = dense(H * (dn + dr), "q_b_proj")(cq).reshape(B, T, H, dn + dr)
        kva = dense(R + dr, "kv_a_proj_with_mqa")(x)
        ckv = norm("kv_a_layernorm")(kva[..., :R])
        k_rope = apply_rope(kva[..., None, R:], positions, cfg.rope_theta)
        q = jnp.concatenate(
            [q[..., :dn], apply_rope(q[..., dn:], positions, cfg.rope_theta)],
            axis=-1)
        kv = dense(H * (dn + dv), "kv_b_proj")(ckv).reshape(B, T, H, dn + dv)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_rope, (B, T, H, dr))], axis=-1)
        keep = selection(GlmDsaIndexer(cfg, name="indexer")(x, cq, positions),
                         cfg.index_topk)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) \
            * (dn + dr) ** -0.5
        p = jax.nn.softmax(jnp.where(keep[:, None], s, -jnp.inf),
                           axis=-1).astype(cfg.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", p, kv[..., dn:])
        return dense(cfg.hidden_size, "o_proj")(out.reshape(B, T, H * dv))


class GlmDsaBlock(nn.Module):
    config: GlmDsaConfig
    index: int

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        x = x + GlmDsaAttention(cfg, name="self_attn")(
            norm("input_layernorm")(x), positions)
        h = norm("post_attention_layernorm")(x)
        if cfg.is_moe_layer(self.index):
            return x + JoyaiMoE(cfg, name="mlp")(h)
        return x + JoyaiMLP(cfg, cfg.intermediate_size, name="mlp")(h)


class GlmDsaForCausalLM(nn.Module):
    config: GlmDsaConfig

    @nn.compact
    def __call__(self, batch, deterministic: bool = True, positions=None):
        """Logits [B, T, V] in float32 (``batch``: ids or ``{"input_ids"}``)."""
        cfg = self.config
        input_ids = batch["input_ids"] if isinstance(batch, dict) else batch
        B, T = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     name="embed_tokens")(input_ids)
        for i in range(cfg.num_hidden_layers):
            x = GlmDsaBlock(cfg, i, name=f"layers_{i}")(x, positions)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                        name="lm_head")(x).astype(jnp.float32)

    def forward_logits(self, input_ids, positions=None):
        return self(input_ids, positions=positions)


__all__ = ["GlmDsaConfig", "GlmDsaForCausalLM"]
