"""NVIDIA Nemotron-H (``model_type: nemotron_h``; Nemotron 3 Nano 30B-A3B) in
flax.linen.

The family is here for its layers: each is ONE block, ``x = x + block(norm(x))``
— a **Mamba-2** mixer (``M``), OR a mixture of routed experts with a shared
expert (``E``), OR grouped-query attention (``*``) — in the order
``hybrid_override_pattern`` gives, where every other hybrid of the zoo pairs
a mixer with a feed-forward in each layer. The serving path is
``inference/v2`` through ``adapt_nemotron_h`` (``adapters/nemotron_h``); this
module gives the parameter tree (``init``) and a plain dense forward.

Layer equations (``chipbench/reference/nemotron_h_ref.py`` states them once
more, in float32). ``x = embed[ids]``; per layer ``x += block(rms_norm(x))``;
``logits = rms_norm(x) W_head`` (untied head):

- ``M``, Mamba-2 on ``u`` with ``H = mamba_num_heads`` heads of ``P =
  mamba_head_dim``, ``E = H P`` (NOT ``expand * hidden_size``), ``N =
  ssm_state_size``, ``G = n_groups`` pairs of ``B``/``C``: ``[z | xBC | dt] =
  in_proj(u)`` (widths ``E``, ``E + 2 G N``, ``H``); ``xBC = silu(conv1d(xBC)
  + b)`` depthwise and causal over ``conv_kernel`` taps; ``dt = softplus(dt +
  dt_bias)`` (no clamp); ``a = -exp(A_log)`` a head; head ``h`` reads group
  ``g(h) = h // (H / G)``: ``S_t[h] = exp(dt_t[h] a[h]) S_{t-1}[h] + dt_t[h]
  x_t[h] (outer) B_t[g(h)]``; ``y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]``;
  ``g = y * silu(z)``; each group's ``E / G`` channels normalised by
  themselves, ``n = g * rsqrt(mean_group(g^2) + eps) * norm`` (the gate
  first, then the norm); ``out_proj(n)``. The recurrence runs in float32
  whatever ``dtype`` is;
- ``E``: ``s = sigmoid(u W_r)`` in float32 over ``n_routed_experts``; the
  ``num_experts_per_tok`` largest of ``s + e_score_correction_bias`` (the bias
  chooses, it does not weigh; ``n_group`` 1: no group restriction); weights
  ``s_i / sum_chosen s`` (``norm_topk_prob``) times ``routed_scaling_factor``;
  expert ``i`` is ``W_down,i relu(W_up,i u)^2`` — two matrices, no gate
  (``mlp_hidden_act`` ``relu2``); plus the shared expert, the same form at
  ``moe_shared_expert_intermediate_size``, every token, unweighted;
- ``*``: ``q, k, v`` without bias and (assumed; the config does not settle it)
  without rotation or any position term; causal softmax of ``q k^T *
  head_dim ** -0.5``, grouped queries, ``o_proj``.

``experts_held = (first, count)``: this module's expert stacks hold only
experts ``first .. first + count - 1`` of the ``n_routed_experts`` the router
scores (one chip's share under expert parallelism); what the absent ones
would add is left out.

Initialisation of what ``normal`` would make degenerate follows Mamba-2's
published one (``models/granite.py``); ``e_score_correction_bias`` is drawn
``normal(0.05)``, small and not zero, so that choosing and weighing differ.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.granite import _a_log_init, ssd_recurrence
from deepspeed_tpu.models.jamba import _dt_bias_init
from deepspeed_tpu.models.llama import RMSNorm

MAMBA, MOE, ATTENTION = "M", "E", "*"
PATTERN_30B_A3B = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclass
class NemotronHConfig:
    """The published ``config.json`` keys under their own names."""
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = PATTERN_30B_A3B
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    attention_bias: bool = False
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8                       # Mamba-2's pairs of B and C
    conv_kernel: int = 4
    chunk_size: int = 128
    expand: int = 2                         # read by nothing: E = heads x dim
    use_conv_bias: bool = True
    mamba_proj_bias: bool = False
    mamba_hidden_act: str = "silu"
    intermediate_size: int = 1856           # a dense MLP layer's (``-``)
    mlp_hidden_act: str = "relu2"
    mlp_bias: bool = False
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    n_shared_experts: int = 1
    moe_shared_expert_intermediate_size: int = 3712
    n_group: int = 1                        # the router's expert groups
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    norm_eps: float = 1e-5
    layer_norm_epsilon: float = 1e-5
    max_position_embeddings: int = 262144
    rope_theta: float = 10000.0             # read by nothing (module docstring)
    partial_rotary_factor: float = 1.0
    tie_word_embeddings: bool = False
    use_bias: bool = False
    time_step_min: float = 1e-3             # initialisation only
    time_step_max: float = 1e-1
    time_step_floor: float = 1e-4
    # (first, count) of the routed experts this model holds; None: all
    experts_held: Optional[Tuple[int, int]] = None
    dtype: Any = jnp.float32
    family: str = "nemotron_h"

    def __post_init__(self):
        pattern = self.hybrid_override_pattern
        if len(pattern) != self.num_hidden_layers \
                or set(pattern) - {MAMBA, MOE, ATTENTION}:
            raise ValueError("hybrid_override_pattern needs one of 'M', 'E', "
                             "'*' for each of num_hidden_layers (a dense MLP "
                             "layer '-' is not built)")
        if self.mamba_num_heads % self.n_groups \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("mamba_num_heads is not a multiple of n_groups, "
                             "or the query heads of the key/value heads")
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError("group-restricted top-k (n_group, topk_group "
                             "> 1): not built")
        if self.mamba_proj_bias or not self.use_conv_bias or self.use_bias \
                or self.attention_bias or self.mlp_bias:
            raise ValueError("a projection, attention or MLP bias, or no "
                             "convolution bias: not built")
        if self.mlp_hidden_act != "relu2" or self.mamba_hidden_act != "silu" \
                or self.tie_word_embeddings or self.n_shared_experts != 1 \
                or not self.norm_topk_prob:
            raise ValueError("another activation than relu2 / silu, a tied "
                             "head, not one shared expert or unnormalised "
                             "routing weights: not built")
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"span of the {self.n_routed_experts} experts")

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.mamba_d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    @classmethod
    def nemotron_3_nano_30b_a3b(cls, **kw):
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw):
        """Every block at toy widths (the state's ``N`` and the Mamba head
        size as published, so the kernels are the real ones; two groups)."""
        d = dict(vocab_size=256, hidden_size=128, num_hidden_layers=7,
                 hybrid_override_pattern="MEM*EME", num_attention_heads=4,
                 num_key_value_heads=2, head_dim=32, mamba_num_heads=4,
                 mamba_head_dim=64, ssm_state_size=128, n_groups=2,
                 chunk_size=128, moe_intermediate_size=64,
                 moe_shared_expert_intermediate_size=128, n_routed_experts=8,
                 num_experts_per_tok=3, max_position_embeddings=512)
        d.update(kw)
        return cls(**d)


def _dense(cfg, feats, name):
    return nn.Dense(feats, use_bias=False, dtype=cfg.dtype, name=name)


def relu2(x):
    return jnp.square(nn.relu(x))


class NemotronHMamba(nn.Module):
    config: NemotronHConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        B, T, _ = u.shape
        H, P, N, K, G = (cfg.mamba_num_heads, cfg.mamba_head_dim,
                         cfg.ssm_state_size, cfg.conv_kernel, cfg.n_groups)
        E, W = H * P, cfg.conv_dim
        zxd = _dense(cfg, E + W + H, "in_proj")(u)
        z, a, dt = zxd[..., :E], zxd[..., E:E + W], zxd[..., E + W:]
        w = self.param("conv_weight",
                       nn.initializers.normal((3 * K) ** -0.5), (W, K),
                       cfg.dtype)
        b = self.param("conv_bias", nn.initializers.zeros, (W,), cfg.dtype)
        pad = jnp.pad(a, ((0, 0), (K - 1, 0), (0, 0)))
        conv = sum(pad[:, j:j + T] * w[:, j] for j in range(K)) + b
        c = nn.silu(conv.astype(jnp.float32)).astype(cfg.dtype)
        f32 = lambda v: v.astype(jnp.float32)
        dt_bias = self.param(
            "dt_bias", _dt_bias_init(cfg.time_step_min, cfg.time_step_max),
            (H,), jnp.float32)
        dt = jax.nn.softplus(f32(dt) + dt_bias)
        a_neg = -jnp.exp(self.param("A_log", _a_log_init, (H,), jnp.float32))
        D = self.param("D", nn.initializers.ones, (H,), jnp.float32)
        y = jax.vmap(ssd_recurrence, in_axes=(0, 0, 0, 0, None, None))(
            dt, f32(c[..., :E]).reshape(B, T, H, P),
            f32(c[..., E:E + G * N]).reshape(B, T, G, N),
            f32(c[..., E + G * N:]).reshape(B, T, G, N), a_neg, D)
        g = (y.reshape(B, T, E) * nn.silu(f32(z))).reshape(B, T, G, E // G)
        gain = self.param("norm", nn.initializers.ones, (E,), cfg.dtype)
        n = (g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                               + cfg.norm_eps)).reshape(B, T, E) * f32(gain)
        return _dense(cfg, cfg.hidden_size, "out_proj")(n.astype(cfg.dtype))


class NemotronHAttention(nn.Module):
    config: NemotronHConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, T, _ = x.shape
        H, Hkv, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        q = _dense(cfg, H * D, "q_proj")(x).reshape(B, T, Hkv, H // Hkv, D)
        k = _dense(cfg, Hkv * D, "k_proj")(x).reshape(B, T, Hkv, D)
        v = _dense(cfg, Hkv * D, "v_proj")(x).reshape(B, T, Hkv, D)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", q, k).astype(jnp.float32) \
            * D ** -0.5
        causal = jnp.tril(jnp.ones((T, T), bool))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        out = jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(cfg.dtype), v)
        return _dense(cfg, cfg.hidden_size, "o_proj")(
            out.reshape(B, T, H * D))


def route(scores, bias, top_k: int, scale: float):
    """Sigmoid scores ``[T, E]`` -> each expert's routing weight ``[T, E]``
    (0 where not chosen): chosen by ``scores + bias``, weighed by the scores
    over the chosen ones' sum, times ``scale``."""
    ids = jax.lax.top_k(scores + bias, top_k)[1]
    on = jnp.sum(jax.nn.one_hot(ids, scores.shape[-1], dtype=jnp.float32),
                 axis=1)
    chosen = scores * on
    return chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20) * scale


class NemotronHMoE(nn.Module):
    """The held routed experts (stacked ``[count, K, N]``, two stacks: no
    gate) plus the shared expert. The dense forward weighs every held expert
    for every token (0 where not chosen): the same sum as a dispatch, at test
    sizes."""

    config: NemotronHConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, T, C = x.shape
        E, F = cfg.n_routed_experts, cfg.moe_intermediate_size
        first, count = cfg.held
        tokens = x.reshape(B * T, C)
        logits = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                          name="router")(tokens.astype(jnp.float32))
        bias = self.param("e_score_correction_bias",
                          nn.initializers.normal(0.05), (E,), jnp.float32)
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=(0,))
        w_up = self.param("w_up", init, (count, C, F), cfg.dtype)
        w_down = self.param("w_down", init, (count, F, C), cfg.dtype)
        dense = route(jax.nn.sigmoid(logits), bias, cfg.num_experts_per_tok,
                      cfg.routed_scaling_factor)[:, first:first + count]

        def add_expert(acc, args):
            wu, wd, wt = args
            y = relu2(tokens @ wu) @ wd
            return acc + y.astype(jnp.float32) * wt[:, None], None

        out, _ = jax.lax.scan(add_expert,
                              jnp.zeros(tokens.shape, jnp.float32),
                              (w_up, w_down, dense.T))
        Fs = cfg.moe_shared_expert_intermediate_size
        shared = _dense(cfg, C, "shared_down")(
            relu2(_dense(cfg, Fs, "shared_up")(tokens)))
        return (out.astype(cfg.dtype) + shared).reshape(B, T, C)


BLOCKS = {MAMBA: NemotronHMamba, MOE: NemotronHMoE,
          ATTENTION: NemotronHAttention}


class NemotronHBlock(nn.Module):
    config: NemotronHConfig
    index: int

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        u = RMSNorm(cfg.norm_eps, cfg.dtype, name="norm")(x)
        kind = cfg.hybrid_override_pattern[self.index]
        return x + BLOCKS[kind](cfg, name="mixer")(u)


class NemotronHForCausalLM(nn.Module):
    config: NemotronHConfig

    @nn.compact
    def __call__(self, batch, deterministic: bool = True):
        """Logits [B, T, V] in float32 (``batch``: ids or ``{"input_ids"}``)."""
        cfg = self.config
        input_ids = batch["input_ids"] if isinstance(batch, dict) else batch
        x = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
            embedding_init=nn.initializers.normal(
                1.0 / math.sqrt(cfg.hidden_size)), name="embed_tokens")(
                    input_ids)
        for i in range(cfg.num_hidden_layers):
            x = NemotronHBlock(cfg, i, name=f"layers_{i}")(x)
        x = RMSNorm(cfg.norm_eps, cfg.dtype, name="norm_f")(x)
        return _dense(cfg, cfg.vocab_size, "lm_head")(x).astype(jnp.float32)

    def forward_logits(self, input_ids):
        return self(input_ids)


__all__ = ["NemotronHConfig", "NemotronHForCausalLM"]
