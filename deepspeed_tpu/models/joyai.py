"""JoyAI-LLM-Flash (``model_type: joyai_llm_flash``) in flax.linen.

The family is here for its attention: multi-head LATENT attention (MLA,
DeepSeek-V2's), whose cache holds one row of ``kv_lora_rank +
qk_rope_head_dim`` values a token a layer — a compressed latent all heads
share and one rotary key — instead of keys and values per head; and for its
feed-forward: a sigmoid router over many small experts (``noaux_tc`` with
one group: choose with ``e_score_correction_bias`` added, weigh without it,
over the chosen scores' sum, times ``routed_scaling_factor``) with a shared
expert, behind one leading dense layer. The serving path is ``inference/v2``
through ``adapt_joyai`` (``adapters/joyai.py``); this module gives the
parameter tree (``init``) and a plain dense forward in the EXPANDED form the
tests hold the engine to.

Layer equations (``chipbench/reference/joyai_ref.py`` states them once more,
in float32), ``h = input_layernorm(x)``:

- ``c_q = q_a_layernorm(h W_qa)``; ``q = c_q W_qb`` -> ``[T, H, nope + rope]``;
- ``[c_kv | k_rope] = h W_kva``; ``c_kv = kv_a_layernorm(c_kv)``; ``k_rope``
  is one rotary key for all heads; rotation over the ``qk_rope_head_dim``
  values of ``q`` and ``k_rope`` only;
- ``[k_nope | v] = c_kv W_kvb`` per head; ``k = [k_nope | k_rope]``; causal
  softmax attention with scale ``(nope + rope) ** -0.5``; ``o_proj``;
- layer 0 (``first_k_dense_replace``): SwiGLU of ``intermediate_size``; the
  others: routed experts of ``moe_intermediate_size`` plus the shared one.

``experts_held = (first, count)`` makes the module ONE chip's share of an
expert-parallel deployment: the router keeps all ``n_routed_experts``
outputs and the published ``num_experts_per_tok``, the expert stacks hold
experts ``first .. first + count - 1`` only, and a token's routed output is
the part its held experts give (what the absent ones would add is left out —
no code stands in for them). ``None`` holds them all.

The multi-token-prediction module the checkpoints carry as layer
``num_hidden_layers`` (``num_nextn_predict_layers``) feeds no logit of the
main model; it is neither built here nor loaded by the adapter.

Rotation pairs ``(x[2i], x[2i+1])`` like the rest of the zoo
(``models/llama.apply_rope``), which is also what ``rope_interleave: true``
says of the checkpoint's layout.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.llama import RMSNorm, _window_bias, apply_rope


@dataclass
class JoyaiConfig:
    """The published ``config.json`` keys under their own names."""
    vocab_size: int = 129280
    hidden_size: int = 2048
    intermediate_size: int = 7168          # the leading dense layer's SwiGLU
    moe_intermediate_size: int = 768       # one expert's (and the shared one's)
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    max_position_embeddings: int = 131072
    rope_theta: float = 32000000.0
    rms_norm_eps: float = 1e-6
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    num_nextn_predict_layers: int = 1
    # (first, count): the routed experts this module holds; None = all
    experts_held: Optional[Tuple[int, int]] = None
    # e_score_correction_bias is a buffer the published training moves to
    # balance load (zero in a fresh model); random weights draw it with a
    # spread that changes selections, so that a router weighing with the
    # biased scores cannot pass for the right one
    e_score_correction_bias_init_std: float = 0.05
    dtype: Any = jnp.float32
    family: str = "joyai"

    def __post_init__(self):
        if self.scoring_func != "sigmoid" or self.topk_method != "noaux_tc" \
                or self.n_group != 1 or self.topk_group != 1:
            raise ValueError(
                "the family publishes scoring_func 'sigmoid', topk_method "
                "'noaux_tc' and one expert group; got "
                f"{self.scoring_func!r}, {self.topk_method!r}, n_group="
                f"{self.n_group}, topk_group={self.topk_group}")
        if self.moe_layer_freq != 1:
            raise ValueError("moe_layer_freq other than 1 is not published")
        if self.experts_held is not None:
            first, count = (int(v) for v in self.experts_held)
            if not (0 <= first and count >= 1
                    and first + count <= self.n_routed_experts):
                raise ValueError(
                    f"experts_held {self.experts_held} lies outside the "
                    f"{self.n_routed_experts} routed experts")
            self.experts_held = (first, count)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    def is_moe_layer(self, i: int) -> bool:
        return i >= self.first_k_dense_replace

    @classmethod
    def joyai_llm_flash(cls, **kw):
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw):
        """A dense layer and three MoE layers at toy widths; the latent row
        is 64 + 16 values."""
        d = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                 moe_intermediate_size=32, num_hidden_layers=4,
                 num_attention_heads=4, num_key_value_heads=4,
                 q_lora_rank=48, kv_lora_rank=64, qk_nope_head_dim=32,
                 qk_rope_head_dim=16, v_head_dim=32,
                 max_position_embeddings=512, rope_theta=10000.0,
                 n_routed_experts=16, num_experts_per_tok=4)
        d.update(kw)
        return cls(**d)


def route(logits: jax.Array, bias: jax.Array, cfg: JoyaiConfig):
    """Router logits [N, E] (float32) -> (weights [N, k], expert ids [N, k])
    over ALL ``n_routed_experts``, whatever this module holds of them."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, ids = jax.lax.top_k(scores + bias.astype(jnp.float32),
                           cfg.num_experts_per_tok)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * cfg.routed_scaling_factor, ids


class JoyaiMLP(nn.Module):
    config: JoyaiConfig
    width: int

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dense = lambda feats, name: nn.Dense(feats, use_bias=False,
                                             dtype=cfg.dtype, name=name)
        return dense(cfg.hidden_size, "down_proj")(
            nn.silu(dense(self.width, "gate_proj")(x))
            * dense(self.width, "up_proj")(x))


class JoyaiMoE(nn.Module):
    """The held routed experts (stacked ``[count, K, N]``) plus the shared
    expert. The dense forward weighs every held expert for every token (0
    where not chosen): the same sum as a dispatch, at test sizes."""

    config: JoyaiConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, T, C = x.shape
        E, F = cfg.n_routed_experts, cfg.moe_intermediate_size
        first, count = cfg.held
        tokens = x.reshape(B * T, C)
        logits = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                          name="gate")(tokens.astype(jnp.float32))
        bias = self.param(
            "e_score_correction_bias",
            nn.initializers.normal(cfg.e_score_correction_bias_init_std),
            (E,), jnp.float32)
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=(0,))
        w_gate = self.param("w_gate", init, (count, C, F), cfg.dtype)
        w_up = self.param("w_up", init, (count, C, F), cfg.dtype)
        w_down = self.param("w_down", init, (count, F, C), cfg.dtype)
        weights, ids = route(logits, bias, cfg)
        dense = jnp.sum(jax.nn.one_hot(ids, E, dtype=jnp.float32)
                        * weights[..., None], axis=1)[:, first:first + count]

        def add_expert(acc, args):
            wg, wu, wd, wt = args
            y = (nn.silu(tokens @ wg) * (tokens @ wu)) @ wd
            return acc + y.astype(jnp.float32) * wt[:, None], None

        out, _ = jax.lax.scan(add_expert,
                              jnp.zeros(tokens.shape, jnp.float32),
                              (w_gate, w_up, w_down, dense.T))
        out = out.astype(cfg.dtype)
        if cfg.n_shared_experts:
            out = out + JoyaiMLP(cfg, F * cfg.n_shared_experts,
                                 name="shared_experts")(tokens)
        return out.reshape(B, T, C)


class JoyaiAttention(nn.Module):
    """MLA, expanded: keys and values of every head from the latent."""
    config: JoyaiConfig

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
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) \
            * (dn + dr) ** -0.5
        p = jax.nn.softmax(s + _window_bias(positions, positions, None),
                           axis=-1).astype(cfg.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", p, kv[..., dn:])
        return dense(cfg.hidden_size, "o_proj")(out.reshape(B, T, H * dv))


class JoyaiBlock(nn.Module):
    config: JoyaiConfig
    index: int

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        x = x + JoyaiAttention(cfg, name="self_attn")(
            norm("input_layernorm")(x), positions)
        h = norm("post_attention_layernorm")(x)
        if cfg.is_moe_layer(self.index):
            return x + JoyaiMoE(cfg, name="mlp")(h)
        return x + JoyaiMLP(cfg, cfg.intermediate_size, name="mlp")(h)


class JoyaiForCausalLM(nn.Module):
    config: JoyaiConfig

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
            x = JoyaiBlock(cfg, i, name=f"layers_{i}")(x, positions)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                        name="lm_head")(x).astype(jnp.float32)

    def forward_logits(self, input_ids, positions=None):
        return self(input_ids, positions=positions)


__all__ = ["JoyaiConfig", "JoyaiForCausalLM"]
