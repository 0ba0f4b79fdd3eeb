"""SDAR (``model_type: sdar_moe``; JetLM SDAR-30B-A3B-Chat) in flax.linen.

The body is the Qwen3-MoE decoder the published ``config.json`` spells out:
grouped-query attention with an RMSNorm over each head's values of q and of
k, rotary positions, and in EVERY layer (``decoder_sparse_step`` 1,
``mlp_only_layers`` empty) a linear router over ``num_experts`` small SwiGLU
experts — softmax over all of them in float32, the ``num_experts_per_tok``
largest, their weights over their own sum (``norm_topk_prob``) — with no
shared expert and no dense layer. What makes it SDAR is how it GENERATES,
which is why it is here: by diffusion over blocks. Attention is causal by
BLOCKS of ``block_length`` positions (``k_pos // B <= q_pos // B``: causal
across blocks, two-way inside one, prompt and generated positions alike), the
logits at a position score the token AT that position, and a block of
``mask_token_id`` tokens is denoised in place
(``inference/v2/blocks/pipeline.py``; docs/SERVING.md "Block-diffusion
generation"; ``chipbench/reference/sdar_ref.py`` states the equations once
more, in float32).

The serving path is ``inference/v2`` through ``adapt_sdar``
(``adapters/sdar.py``); this module gives the parameter tree in the published
layout (``init``) and a plain dense forward under the block rule the tests
hold the engine to.

Rotation pairs ``(x[2i], x[2i+1])`` like the rest of the zoo
(``models/llama.apply_rope``); the published code pairs ``(x[i], x[i+D/2])``,
which is the same function after a fixed permutation of each head's q/k
columns (and norm gains) at conversion (``module_inject/containers.py``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.llama import RMSNorm, apply_rope


@dataclass
class SdarMoeConfig:
    """The published ``config.json`` keys under their own names, and the two
    the family's ``generate.py`` takes as arguments."""
    vocab_size: int = 151936
    hidden_size: int = 2048
    intermediate_size: int = 6144          # published; no layer is dense
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    max_position_embeddings: int = 32768
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: tuple = ()
    # generation by diffusion over blocks: the block (a power of two) and the
    # token a not-yet-denoised position holds (inside the vocabulary)
    block_length: int = 4
    mask_token_id: int = 151669
    dtype: Any = jnp.float32
    family: str = "sdar_moe"

    def __post_init__(self):
        self.mlp_only_layers = tuple(self.mlp_only_layers)
        if self.decoder_sparse_step != 1 or self.mlp_only_layers:
            raise ValueError("the family publishes every layer as a MoE layer "
                             "(decoder_sparse_step 1, mlp_only_layers [])")
        if not self.norm_topk_prob:
            raise ValueError("norm_topk_prob false is not the family's")
        B = self.block_length
        if B < 1 or B & (B - 1):
            raise ValueError(f"block_length must be a power of two, got {B}")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(f"mask_token_id {self.mask_token_id} lies outside "
                             f"the vocabulary of {self.vocab_size}")

    @classmethod
    def sdar_30b_a3b(cls, **kw):
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw):
        d = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                 moe_intermediate_size=32, num_hidden_layers=2,
                 num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                 max_position_embeddings=256, num_experts=8,
                 num_experts_per_tok=2, mask_token_id=255)
        d.update(kw)
        return cls(**d)


def route(logits: jax.Array, cfg: SdarMoeConfig):
    """Router logits [N, E] -> (weights [N, k], expert ids [N, k]): softmax
    over all experts in float32, the k largest, over their own sum."""
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, ids = jax.lax.top_k(p, cfg.num_experts_per_tok)
    return w / jnp.sum(w, axis=-1, keepdims=True), ids


class SdarMoE(nn.Module):
    """Routed SwiGLU experts (stacked ``[E, K, N]``, as Mixtral's are),
    evaluated through the grouped GEMM, sorted by expert."""

    config: SdarMoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, T, C = x.shape
        E, k, F = cfg.num_experts, cfg.num_experts_per_tok, \
            cfg.moe_intermediate_size
        tokens = x.reshape(B * T, C)
        logits = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                          name="gate")(tokens.astype(jnp.float32))
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=(0,))
        w_gate = self.param("w_gate", init, (E, C, F), cfg.dtype)
        w_up = self.param("w_up", init, (E, C, F), cfg.dtype)
        w_down = self.param("w_down", init, (E, F, C), cfg.dtype)
        weights, ids = route(logits, cfg)

        flat_e = ids.reshape(-1)
        order = jnp.argsort(flat_e)
        rows = tokens[jnp.repeat(jnp.arange(B * T), k)[order]]
        sizes = jnp.bincount(flat_e, length=E).astype(jnp.int32)
        h = nn.silu(jax.lax.ragged_dot(rows, w_gate, sizes)) \
            * jax.lax.ragged_dot(rows, w_up, sizes)
        ys = jax.lax.ragged_dot(h, w_down, sizes)
        ys = ys * weights.reshape(-1)[order][:, None].astype(ys.dtype)
        out = ys[jnp.argsort(order)].reshape(B * T, k, C).sum(axis=1)
        return out.reshape(B, T, C)


def block_causal_bias(positions: jax.Array, block: int) -> jax.Array:
    """Additive bias [B, 1, Tq, Tk]: key s is visible to query t iff
    ``s // block <= t // block``."""
    blk = positions // block
    ok = blk[:, None, :] <= blk[:, :, None]
    return jnp.where(ok, 0.0, jnp.finfo(jnp.float32).min)[:, None]


class SdarAttention(nn.Module):
    config: SdarMoeConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        B, T, _ = x.shape
        H, Hkv, D = cfg.num_attention_heads, cfg.num_key_value_heads, \
            cfg.head_dim
        dense = lambda feats, name: nn.Dense(feats, use_bias=False,
                                             dtype=cfg.dtype, name=name)
        q = dense(H * D, "q_proj")(x).reshape(B, T, H, D)
        k = dense(Hkv * D, "k_proj")(x).reshape(B, T, Hkv, D)
        v = dense(Hkv * D, "v_proj")(x).reshape(B, T, Hkv, D)
        q = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="q_norm")(q)
        k = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="k_norm")(k)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        bias = block_causal_bias(positions, cfg.block_length)
        qg = q.reshape(B, T, Hkv, H // Hkv, D)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k).astype(jnp.float32) \
            / (D ** 0.5)
        p = jax.nn.softmax(s + bias[:, :, None], axis=-1).astype(cfg.dtype)
        out = jnp.einsum("bgrqk,bkgd->bqgrd", p, v).reshape(B, T, H * D)
        return dense(cfg.hidden_size, "o_proj")(out)


class SdarBlock(nn.Module):
    config: SdarMoeConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        x = x + SdarAttention(cfg, name="self_attn")(
            norm("input_layernorm")(x), positions)
        return x + SdarMoE(cfg, name="mlp")(
            norm("post_attention_layernorm")(x))


class SdarMoeForCausalLM(nn.Module):
    config: SdarMoeConfig

    @nn.compact
    def __call__(self, batch, deterministic: bool = True, positions=None):
        """Logits [B, T, V] in float32 under the block rule; row t scores
        the token AT t (``batch``: ids or ``{"input_ids"}``)."""
        cfg = self.config
        input_ids = batch["input_ids"] if isinstance(batch, dict) else batch
        B, T = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     name="embed_tokens")(input_ids)
        for i in range(cfg.num_hidden_layers):
            x = SdarBlock(cfg, name=f"layers_{i}")(x, positions)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                        name="lm_head")(x).astype(jnp.float32)

    def forward_logits(self, input_ids, positions=None):
        return self(input_ids, positions=positions)


__all__ = ["SdarMoeConfig", "SdarMoeForCausalLM"]
