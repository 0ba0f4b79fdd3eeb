"""Qwen3-Next (``model_type: qwen3_next``; Qwen3-Next-80B-A3B) in flax.linen.

The family is here for its mixers: three layers of four are **Gated
DeltaNet** — a linear-attention layer whose state a head is a matrix
corrected by a delta rule — and the fourth is softmax attention with an
output gate, 256-wide heads and a quarter of each head rotated; every layer's
feed-forward is a mixture of 512 small experts, top-10, beside a shared
expert behind a sigmoid gate. The serving path is ``inference/v2`` through
``adapt_qwen3_next`` (``adapters/qwen3_next``); this module gives the parameter
tree in the published layout (``init``) and a plain dense forward.

Layer equations (``chipbench/reference/qwen3_next_ref.py`` states them once
more, in float32). ``N(x; w) = x * rsqrt(mean(x^2) + eps) * (1 + w)`` for
every norm but the Gated DeltaNet's own. ``h = embed[ids]``; per layer ``h +=
mixer(N(h)); h += moe(N(h))``; ``logits = N(h) W_head`` (untied head):

- attention where ``(l + 1) % full_attention_interval == 0``: ``q_proj(x)``
  viewed ``[H, 2 D]`` gives a head's ``q`` and its gate; ``k``, ``v`` ``[Hkv,
  D]``; ``q``, ``k`` normed over each head's ``D``; the first ``D *
  partial_rotary_factor`` values of each head rotated (half-split pairing);
  causal softmax of ``q k^T * D ** -0.5``, grouped queries; ``o_proj(attn *
  sigmoid(gate))``;
- Gated DeltaNet elsewhere, ``Hk`` key heads of ``N``, ``Hv`` value heads of
  ``P``: ``in_proj_qkvz(x)`` viewed ``[Hk, 2 N + 2 (Hv / Hk) P]`` gives a key
  head's ``q``, ``k`` and its value heads' ``v`` and ``z``; ``in_proj_ba(x)``
  viewed ``[Hk, 2 Hv / Hk]`` their ``b`` and ``a``; ``(q, k, v)`` flattened
  pass a causal depthwise convolution of ``linear_conv_kernel_dim`` taps (no
  bias) and SiLU; ``q``, ``k`` L2-normalised a head, ``q *= N ** -0.5``;
  ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; the state
  a value head ``S`` ``[N, P]``: ``S' = exp(g_t) S_{t-1}``; ``S_t = S' +
  beta_t k_t (v_t - S'^T k_t)^T``; ``o_t = S_t^T q_t``; ``y = rms_norm(o_t;
  norm) * silu(z)`` over each head's ``P``, the norm first, then the gate
  (plain gain); ``out_proj(y)``. The recurrence runs in float32 whatever
  ``dtype`` is;
- the experts: ``p = softmax(x W_r)`` in float32 over ``num_experts``; the
  ``num_experts_per_tok`` largest, renormalised to sum 1 (``norm_topk_prob``);
  expert ``i`` a SwiGLU of ``moe_intermediate_size``; plus ``sigmoid(x .
  w_sg)`` times the shared expert's SwiGLU.

``experts_held = (first, count)``: this module's expert stacks hold only
experts ``first .. first + count - 1`` of the ``num_experts`` the router
scores (one chip's share under expert parallelism); what the absent ones
would add is left out. The multi-token-prediction module (``mtp.*``) is not
built: the published modelling code ignores it when it serves without
drafts.

Initialisation: matrices lecun-normal, norm weights zero (``1 + w``; the
mixer's own one), the convolution's taps as PyTorch's Conv1d default,
``dt_bias`` ones and ``A_log = log U(0, 16)`` as the published modelling
code draws them — except that one value head in four (``slow_heads``) draws
``A`` from ``U(0, 16) / 1024`` instead (``exp(g)`` about 0.98 to 1): with
``A`` up to 16 most heads forget within a few tokens, and a state that is
gone in three tokens hides an error in how it is carried.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.llama import RMSNorm, rope_frequencies


@dataclass
class Qwen3NextConfig:
    """The published ``config.json`` keys under their own names."""
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    rope_scaling: Optional[dict] = None
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    hidden_act: str = "silu"
    intermediate_size: int = 5120           # a dense layer's: none is built
    decoder_sparse_step: int = 1
    mlp_only_layers: Tuple[int, ...] = ()
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    use_sliding_window: bool = False
    # (first, count) of the routed experts this model holds; None: all
    experts_held: Optional[Tuple[int, int]] = None
    # chunk of the serving path's chunked delta-rule scan (no mathematics)
    chunk_size: int = 64
    # one value head in this many draws a small decay rate (module docstring)
    slow_heads: int = 4
    dtype: Any = jnp.float32
    family: str = "qwen3_next"

    def __post_init__(self):
        self.mlp_only_layers = tuple(self.mlp_only_layers)
        if self.mlp_only_layers or self.decoder_sparse_step != 1 \
                or self.use_sliding_window or self.rope_scaling \
                or self.tie_word_embeddings or not self.norm_topk_prob \
                or self.hidden_act != "silu":
            raise ValueError("dense-MLP layers, a sliding window, rope "
                             "scaling, a tied head, unnormalised routing "
                             "weights or another activation: not built")
        if self.linear_num_value_heads % self.linear_num_key_heads \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("the value heads are not a multiple of the key "
                             "heads, or the query heads of the key/value "
                             "heads")
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"span of the {self.num_experts} experts")

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_experts)

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_dim + self.value_dim

    def is_attention_layer(self, i: int) -> bool:
        return (i + 1) % self.full_attention_interval == 0

    @classmethod
    def qwen3_next_80b_a3b(cls, **kw):
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw):
        """Two periods at toy widths (the delta-rule heads as published, 128
        x 128, so the kernels are the real ones)."""
        d = dict(vocab_size=256, hidden_size=128, num_hidden_layers=8,
                 num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                 linear_num_key_heads=1, linear_num_value_heads=2,
                 num_experts=8, num_experts_per_tok=3,
                 moe_intermediate_size=64, shared_expert_intermediate_size=64,
                 max_position_embeddings=512, rope_theta=10000.0)
        d.update(kw)
        return cls(**d)


def _dense(cfg, feats, name):
    return nn.Dense(feats, use_bias=False, dtype=cfg.dtype, name=name)


def _norm(cfg, name):
    return RMSNorm(cfg.rms_norm_eps, cfg.dtype, plus_one=True, name=name)


def rope_half(x, positions, theta: float, rotary_dim: int):
    """Rotation of the first ``rotary_dim`` values of each head of ``x``
    ``[.., T, H, D]`` by ``positions`` ``[.., T]``, value ``i`` paired with
    value ``i + rotary_dim / 2`` (the half-split pairing)."""
    half = rotary_dim // 2
    angles = positions[..., None].astype(jnp.float32) \
        * rope_frequencies(rotary_dim, theta)
    cos, sin = jnp.cos(angles)[..., None, :], jnp.sin(angles)[..., None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:rotary_dim].astype(jnp.float32)
    rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([rot.astype(x.dtype), x[..., rotary_dim:]],
                           axis=-1)


def split_qkvz(qkvz, ba, cfg: Qwen3NextConfig):
    """The fused projections' outputs ``[.., Hk (2 N + 2 R P)]`` and ``[..,
    Hk 2 R]`` (``R = Hv / Hk``) -> ``(q [.., Hk, N], k [.., Hk, N], v [..,
    Hv, P], z [.., Hv, P], b [.., Hv], a [.., Hv])``: a key head's values lie
    together, its value heads' after them."""
    Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    N, P, R = cfg.linear_key_head_dim, cfg.linear_value_head_dim, Hv // Hk
    lead = qkvz.shape[:-1]
    x = qkvz.reshape(lead + (Hk, 2 * N + 2 * R * P))
    y = ba.reshape(lead + (Hk, 2 * R))
    heads = lambda v: v.reshape(lead + (Hv, P))
    return (x[..., :N], x[..., N:2 * N], heads(x[..., 2 * N:2 * N + R * P]),
            heads(x[..., 2 * N + R * P:]), y[..., :R].reshape(lead + (Hv,)),
            y[..., R:].reshape(lead + (Hv,)))


def _a_log_init(slow_heads: int):
    """``A_log = log U(0, 16)`` a value head, one head in ``slow_heads`` from
    ``U(0, 16) / 1024`` (the module's docstring)."""
    def init(key, shape, dtype=jnp.float32):
        A = jax.random.uniform(key, shape, jnp.float32, 1e-4, 16.0)
        if slow_heads:
            slow = jnp.arange(shape[0]) % slow_heads == 0
            A = jnp.where(slow, (A + 1e-2) / 1024.0, A)
        return jnp.log(A).astype(dtype)
    return init


def delta_recurrence(q, k, v, g, beta):
    """The gated delta rule token by token, in float32, from a zero state:
    ``q``, ``k`` ``[T, Hv, N]``, ``v`` ``[T, Hv, P]``, ``g``, ``beta`` ``[T,
    Hv]`` -> ``o`` ``[T, Hv, P]``."""
    def step(S, row):
        q_t, k_t, v_t, g_t, b_t = row
        S = jnp.exp(g_t)[:, None, None] * S
        w = b_t[:, None] * (v_t - jnp.einsum("hnp,hn->hp", S, k_t))
        S = S + k_t[:, :, None] * w[:, None, :]
        return S, jnp.einsum("hnp,hn->hp", S, q_t)

    S0 = jnp.zeros(k.shape[1:] + v.shape[2:], jnp.float32)
    return jax.lax.scan(step, S0, (q, k, v, g, beta))[1]


class Qwen3NextGatedDeltaNet(nn.Module):
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        B, T, _ = u.shape
        Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        N, P, K = (cfg.linear_key_head_dim, cfg.linear_value_head_dim,
                   cfg.linear_conv_kernel_dim)
        f32 = lambda x: x.astype(jnp.float32)
        q, k, v, z, b, a = split_qkvz(
            _dense(cfg, 2 * cfg.key_dim + 2 * cfg.value_dim,
                   "in_proj_qkvz")(u),
            _dense(cfg, 2 * Hv, "in_proj_ba")(u), cfg)
        flat = lambda x: x.reshape(B, T, -1)
        mixed = jnp.concatenate([flat(q), flat(k), flat(v)], axis=-1)
        w = self.param("conv_weight", nn.initializers.normal((3 * K) ** -0.5),
                       (cfg.conv_dim, K), cfg.dtype)
        pad = jnp.pad(mixed, ((0, 0), (K - 1, 0), (0, 0)))
        c = nn.silu(f32(sum(pad[:, j:j + T] * w[:, j] for j in range(K)))
                    ).astype(cfg.dtype)
        unit = lambda x: x * jax.lax.rsqrt(
            jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
        per_v = lambda x: jnp.repeat(x, Hv // Hk, axis=2)
        q = per_v(unit(f32(c[..., :cfg.key_dim]).reshape(B, T, Hk, N))) \
            * N ** -0.5
        k = per_v(unit(f32(c[..., cfg.key_dim:2 * cfg.key_dim]
                           ).reshape(B, T, Hk, N)))
        v = f32(c[..., 2 * cfg.key_dim:]).reshape(B, T, Hv, P)
        dt_bias = self.param("dt_bias", nn.initializers.ones, (Hv,),
                             jnp.float32)
        A_log = self.param("A_log", _a_log_init(cfg.slow_heads), (Hv,),
                           jnp.float32)
        g = -jnp.exp(A_log) * jax.nn.softplus(f32(a) + dt_bias)
        o = jax.vmap(delta_recurrence)(q, k, v, g, jax.nn.sigmoid(f32(b)))
        gain = self.param("norm", nn.initializers.ones, (P,), cfg.dtype)
        n = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + cfg.rms_norm_eps) * f32(gain)
        y = (n * nn.silu(f32(z))).reshape(B, T, Hv * P)
        return _dense(cfg, cfg.hidden_size, "out_proj")(y.astype(cfg.dtype))


class Qwen3NextAttention(nn.Module):
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, T, _ = x.shape
        H, Hkv, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        qg = _dense(cfg, H * 2 * D, "q_proj")(x).reshape(B, T, H, 2 * D)
        q, gate = qg[..., :D], qg[..., D:].reshape(B, T, H * D)
        k = _dense(cfg, Hkv * D, "k_proj")(x).reshape(B, T, Hkv, D)
        v = _dense(cfg, Hkv * D, "v_proj")(x).reshape(B, T, Hkv, D)
        positions = jnp.broadcast_to(jnp.arange(T), (B, T))
        rot = lambda y: rope_half(y, positions, cfg.rope_theta,
                                  cfg.rotary_dim)
        q = rot(_norm(cfg, "q_norm")(q)).reshape(B, T, Hkv, H // Hkv, D)
        k = rot(_norm(cfg, "k_norm")(k))
        s = jnp.einsum("bqgrd,bkgd->bgrqk", q, k).astype(jnp.float32) \
            * D ** -0.5
        causal = jnp.tril(jnp.ones((T, T), bool))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        out = jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(cfg.dtype), v)
        out = out.reshape(B, T, H * D) \
            * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(cfg.dtype)
        return _dense(cfg, cfg.hidden_size, "o_proj")(out)


class _SwiGLU(nn.Module):
    config: Qwen3NextConfig
    width: int

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        return _dense(cfg, x.shape[-1], "down_proj")(
            nn.silu(_dense(cfg, self.width, "gate_proj")(x))
            * _dense(cfg, self.width, "up_proj")(x))


class Qwen3NextMoE(nn.Module):
    """The held routed experts (stacked ``[count, K, N]``) plus the gated
    shared expert. The dense forward weighs every held expert for every token
    (0 where not chosen): the same sum as a dispatch, at test sizes."""

    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, T, C = x.shape
        E, F = cfg.num_experts, cfg.moe_intermediate_size
        first, count = cfg.held
        tokens = x.reshape(B * T, C)
        logits = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                          name="gate")(tokens.astype(jnp.float32))
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=(0,))
        w_gate = self.param("w_gate", init, (count, C, F), cfg.dtype)
        w_up = self.param("w_up", init, (count, C, F), cfg.dtype)
        w_down = self.param("w_down", init, (count, F, C), cfg.dtype)
        top, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                 cfg.num_experts_per_tok)
        top = top / jnp.sum(top, axis=-1, keepdims=True)
        dense = jnp.sum(jax.nn.one_hot(ids, E, dtype=jnp.float32)
                        * top[..., None], axis=1)[:, first:first + count]

        def add_expert(acc, args):
            wg, wu, wd, wt = args
            y = (nn.silu(tokens @ wg) * (tokens @ wu)) @ wd
            return acc + y.astype(jnp.float32) * wt[:, None], None

        out, _ = jax.lax.scan(add_expert,
                              jnp.zeros(tokens.shape, jnp.float32),
                              (w_gate, w_up, w_down, dense.T))
        shared = _SwiGLU(cfg, cfg.shared_expert_intermediate_size,
                         name="shared_expert")(tokens)
        on = jax.nn.sigmoid(_dense(cfg, 1, "shared_expert_gate")(tokens)
                            .astype(jnp.float32))
        return (out + on * shared.astype(jnp.float32)).astype(
            cfg.dtype).reshape(B, T, C)


class Qwen3NextLayer(nn.Module):
    config: Qwen3NextConfig
    index: int

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        u = _norm(cfg, "input_layernorm")(x)
        if cfg.is_attention_layer(self.index):
            x = x + Qwen3NextAttention(cfg, name="self_attn")(u)
        else:
            x = x + Qwen3NextGatedDeltaNet(cfg, name="linear_attn")(u)
        return x + Qwen3NextMoE(cfg, name="mlp")(
            _norm(cfg, "post_attention_layernorm")(x))


class Qwen3NextForCausalLM(nn.Module):
    config: Qwen3NextConfig

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
            x = Qwen3NextLayer(cfg, i, name=f"layers_{i}")(x)
        x = _norm(cfg, "norm")(x)
        return _dense(cfg, cfg.vocab_size, "lm_head")(x).astype(jnp.float32)

    def forward_logits(self, input_ids):
        return self(input_ids)


__all__ = ["Qwen3NextConfig", "Qwen3NextForCausalLM"]
