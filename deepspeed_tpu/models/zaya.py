"""ZAYA1 (``model_type: zaya``; Zyphra ZAYA1-8B) in flax.linen.

The family is here for two mechanisms no other family of the zoo has:
**compressed convolutional attention** (CCA: the query and key projections
are mixed along the sequence by two small causal convolutions before they
attend, and one of the two value heads is the PREVIOUS token's) and a
**router that is an MLP with a state**: a 256-wide stream that every layer's
router adds to and hands to the next layer's, choosing one of 16 experts or
none at all. The serving path is ``inference/v2`` through ``adapt_zaya``
(``adapters/zaya.py``); this module gives the parameter tree in the published
layout (``init``) and a plain dense forward.

Layer equations (``chipbench/reference/zaya_ref.py`` states them once more,
in float32, and says which details the published configuration does not pin:
its ``assumed`` list). ``n(.)`` an RMSNorm with a plain gain, ``a*``, ``c*``
learned vectors of the hidden size (``residual_scale`` / ``residual_bias``,
rows 0-3)::

    x'  = (a0 * x  + c0) + (a1 * CCA(n1(x))        + c1)
    x'' = (a2 * x' + c2) + (a3 * MoE(n2(x'), r_in) + c3)

- CCA on ``u = n1(x)``, ``Hq`` query heads over ``Hk`` key/value heads of
  ``d``, ``G = Hq / Hk``: ``qp = u Wq``, ``kp = u Wk``, ``v1 = u Wv1``, ``z =
  u Wv2`` (``d`` each, the last two); ``s = [qp ; kp]`` passes a causal
  depthwise convolution of ``cca_time0`` taps and then a causal convolution
  of ``cca_time1`` taps grouped by head (a ``[d, d]`` block a head a tap),
  both with a bias and no activation, the input left-padded with zeros; ``q_j
  = y^q_j + (qp_j + kp_{j // G}) / 2``, ``k_i = y^k_i + (kp_i + mean_{j in
  i} qp_j) / 2``; both normed to ``sqrt(d)`` a head, ``k`` times a learned
  temperature a head; the first ``d * partial_rotary_factor`` values of each
  head rotated (half-split pairing); key/value head 0's value is ``v1_t``,
  head 1's ``z_{t-1}``; causal softmax of ``q k^T * d ** -0.5``, grouped
  queries; ``o_proj``;
- the router on ``g = n2(x')``: ``r = g Wd + bd`` (``router_hidden_size``
  wide), plus ``gamma * r_in`` in every layer but the first; ``logits = W3
  gelu(W2 gelu(W1 nr(r) + b1) + b2)`` over ``num_experts + 1`` choices, in
  float32; ``p = softmax(logits)``; ``e = argmax(p + beta)``; the layer adds
  ``p_e * SwiGLU_e(g)``, or nothing where ``e`` is the last choice (the
  token skips the experts); ``r`` goes on to the next layer.

Initialisation: matrices lecun-normal, norm gains one, the convolutions'
taps and biases normal with the variance of PyTorch's ``Conv1d`` default;
``residual_scale = 1 + N(0, 0.02)``, ``residual_bias = N(0, 0.02)``; the
temperature ``U(0.5, 1.5)``; ``gamma`` ``U(0.25, 0.75)`` (a carry of ``r``
that is wrong must show); ``beta = N(0, 0.01)`` with the skip choice's at
``-0.05``, so that a few percent of the tokens skip.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.llama import RMSNorm
from deepspeed_tpu.models.qwen3_next import rope_half


@dataclass
class ZayaConfig:
    """The published ``config.json`` keys under their own names."""
    vocab_size: int = 262272
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2
    cca_time1: int = 2
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5e6             # rope_parameters.hybrid.rope_theta
    hidden_act: str = "silu"
    num_experts: int = 16
    num_experts_per_tok: int = 1
    moe_intermediate_size: int = 2048
    router_hidden_size: int = 256
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = True
    attention_bias: bool = False
    lm_head_bias: bool = False
    sliding_window: Optional[int] = None
    layer_types: Optional[Tuple[str, ...]] = None   # every layer "hybrid"
    dtype: Any = jnp.float32
    family: str = "zaya"

    def __post_init__(self):
        kinds = tuple(self.layer_types or ("hybrid",) * self.num_hidden_layers)
        self.layer_types = kinds
        if set(kinds) - {"hybrid"} or len(kinds) != self.num_hidden_layers \
                or self.sliding_window is not None or self.attention_bias \
                or self.lm_head_bias or not self.tie_word_embeddings \
                or self.hidden_act != "silu" or self.num_experts_per_tok != 1:
            raise ValueError("a layer that is not 'hybrid', a sliding window, "
                             "a bias, an untied head, another activation or "
                             "more than one expert a token: not built")
        if self.num_key_value_heads != 2 \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("the value shift gives key/value head 0 the "
                             "token's own value and head 1 the previous "
                             "token's: two key/value heads, and query heads "
                             "a multiple of them")
        if self.cca_time0 < 1 or self.cca_time1 < 1:
            raise ValueError("a convolution of no taps")

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def conv_dim(self) -> int:
        """The channels the two convolutions mix: q and k of every head."""
        return (self.num_attention_heads + self.num_key_value_heads) \
            * self.head_dim

    @property
    def tail_taps(self) -> int:
        """How many earlier tokens a token's q and k read."""
        return max(1, self.cca_time0 + self.cca_time1 - 2)

    @classmethod
    def zaya1_8b(cls, **kw):
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw):
        """Three layers at toy widths; the heads as published (128 wide), so
        the paged kernels are the real ones."""
        d = dict(vocab_size=256, hidden_size=128, num_hidden_layers=3,
                 num_attention_heads=4, num_key_value_heads=2, head_dim=128,
                 num_experts=4, moe_intermediate_size=128,
                 router_hidden_size=32, max_position_embeddings=512,
                 rope_theta=10000.0)
        d.update(kw)
        return cls(**d)


def _dense(cfg, feats, name, bias=False, dtype=None):
    return nn.Dense(feats, use_bias=bias, dtype=dtype or cfg.dtype, name=name)


def _uniform(lo: float, hi: float):
    return lambda key, shape, dtype=jnp.float32: jax.random.uniform(
        key, shape, jnp.float32, lo, hi).astype(dtype)


def _around(mean: float, std: float):
    return lambda key, shape, dtype=jnp.float32: (
        mean + std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _balancing_bias(key, shape, dtype=jnp.float32):
    """``N(0, 0.01)`` an expert, the skip choice (the last) at ``-0.05``."""
    b = 0.01 * jax.random.normal(key, shape, jnp.float32)
    return b.at[-1].set(-0.05).astype(dtype)


def cca_mix(s, w0, b0, w1, b1):
    """The two causal convolutions over ``s`` ``[B, T, C]``, the INPUT
    left-padded with zeros: depthwise ``w0`` ``[C, K0]``, then grouped by
    head ``w1`` ``[C, d, K1]`` (PyTorch's grouped ``Conv1d`` layout: output
    channel, input channel of its group, tap), both with a bias, no
    activation. Float32."""
    B, T, C = s.shape
    K0, (_, d, K1) = w0.shape[1], w1.shape
    f32 = lambda x: x.astype(jnp.float32)
    pad = jnp.pad(f32(s), ((0, 0), (K0 + K1 - 2, 0), (0, 0)))
    n = T + K1 - 1
    m = f32(b0) + sum(pad[:, j:j + n] * f32(w0)[:, j] for j in range(K0))
    m = m.reshape(B, n, C // d, d)
    blocks = f32(w1).reshape(C // d, d, d, K1)       # [head, out, in, tap]
    y = sum(jnp.einsum("bthi,hoi->btho", m[:, j:j + T], blocks[..., j])
            for j in range(K1))
    return y.reshape(B, T, C) + f32(b1)


class ZayaCCA(nn.Module):
    config: ZayaConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        B, T, _ = u.shape
        Hq, Hk, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        G, C = Hq // Hk, cfg.conv_dim
        f32 = lambda x: x.astype(jnp.float32)
        qp = _dense(cfg, Hq * d, "q_proj")(u)
        kp = _dense(cfg, Hk * d, "k_proj")(u)
        v1 = _dense(cfg, d, "v_proj")(u)
        z = _dense(cfg, d, "v_prev_proj")(u)
        K0, K1 = cfg.cca_time0, cfg.cca_time1
        w0 = self.param("conv0_weight", nn.initializers.normal(
            (3 * K0) ** -0.5), (C, K0), cfg.dtype)
        b0 = self.param("conv0_bias", nn.initializers.normal(
            (3 * K0) ** -0.5), (C,), cfg.dtype)
        w1 = self.param("conv1_weight", nn.initializers.normal(
            (3 * K1 * d) ** -0.5), (C, d, K1), cfg.dtype)
        b1 = self.param("conv1_bias", nn.initializers.normal(
            (3 * K1 * d) ** -0.5), (C,), cfg.dtype)
        temp = self.param("temp", _uniform(0.5, 1.5), (Hk,), jnp.float32)
        y = cca_mix(jnp.concatenate([qp, kp], axis=-1), w0, b0, w1, b1)
        qh, kh = f32(qp).reshape(B, T, Hk, G, d), f32(kp).reshape(B, T, Hk, d)
        q = y[..., :Hq * d].reshape(B, T, Hk, G, d) \
            + (qh + kh[:, :, :, None]) / 2
        k = y[..., Hq * d:].reshape(B, T, Hk, d) + (kh + qh.mean(axis=3)) / 2
        unit = lambda x: x * jax.lax.rsqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + cfg.rms_norm_eps)
        q, k = unit(q), unit(k) * temp[:, None]
        positions = jnp.broadcast_to(jnp.arange(T), (B, T))
        rot = lambda x: rope_half(x, positions, cfg.rope_theta,
                                  cfg.rotary_dim)
        q = rot(q.reshape(B, T, Hq, d)).reshape(B, T, Hk, G, d)
        k = rot(k)
        z_prev = jnp.pad(z, ((0, 0), (1, 0), (0, 0)))[:, :T]
        v = f32(jnp.stack([v1, z_prev], axis=2))             # [B, T, 2, d]
        s = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) * d ** -0.5
        causal = jnp.tril(jnp.ones((T, T), bool))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        out = jnp.einsum("bgrqk,bkgd->bqgrd", p, v).reshape(B, T, Hq * d)
        return _dense(cfg, cfg.hidden_size, "o_proj")(out.astype(cfg.dtype))


class ZayaMoE(nn.Module):
    """The router MLP with its state, and the experts (stacked ``[E, K,
    N]``). The dense forward weighs every expert for every token (0 where
    not chosen): the same sum as a dispatch, at test sizes. Returns ``(the
    branch, the router's state for the next layer)``."""

    config: ZayaConfig
    index: int

    @nn.compact
    def __call__(self, x, r_in):
        cfg = self.config
        B, T, C = x.shape
        E, F, R = (cfg.num_experts, cfg.moe_intermediate_size,
                   cfg.router_hidden_size)
        f32 = jnp.float32
        tokens = x.reshape(B * T, C)
        dense = lambda n, name, bias=True: _dense(cfg, n, name, bias, f32)
        r = dense(R, "router_down")(tokens.astype(f32))
        gamma = self.param("router_state_scale", _uniform(0.25, 0.75), (R,),
                           f32)
        if self.index > 0:
            r = r + gamma * r_in
        h = RMSNorm(cfg.rms_norm_eps, f32, name="router_norm")(r)
        h = nn.gelu(dense(R, "router_fc1")(h), approximate=False)
        h = nn.gelu(dense(R, "router_fc2")(h), approximate=False)
        p = jax.nn.softmax(dense(E + 1, "router_out", False)(h), axis=-1)
        beta = self.param("balancing_bias", _balancing_bias, (E + 1,), f32)
        e = jnp.argmax(p + beta, axis=-1)
        weight = jnp.take_along_axis(p, e[:, None], axis=-1)
        onto = jax.nn.one_hot(e, E + 1, dtype=f32)[:, :E] * weight   # [T, E]
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=(0,))
        w_gate = self.param("w_gate", init, (E, C, F), cfg.dtype)
        w_up = self.param("w_up", init, (E, C, F), cfg.dtype)
        w_down = self.param("w_down", init, (E, F, C), cfg.dtype)

        def add_expert(acc, args):
            wg, wu, wd, wt = args
            y = (nn.silu(tokens @ wg) * (tokens @ wu)) @ wd
            return acc + y.astype(f32) * wt[:, None], None

        out, _ = jax.lax.scan(add_expert, jnp.zeros(tokens.shape, f32),
                              (w_gate, w_up, w_down, onto.T))
        return out.astype(cfg.dtype).reshape(B, T, C), r


class ZayaLayer(nn.Module):
    config: ZayaConfig
    index: int

    @nn.compact
    def __call__(self, x, r_in=None):
        cfg = self.config
        hid = cfg.hidden_size
        a = self.param("residual_scale", _around(1.0, 0.02), (4, hid),
                       cfg.dtype).astype(jnp.float32)
        c = self.param("residual_bias", _around(0.0, 0.02), (4, hid),
                       cfg.dtype).astype(jnp.float32)
        join = lambda i, x, y: ((a[i] * x + c[i]) + (a[i + 1] * y + c[i + 1])
                                ).astype(cfg.dtype)
        if r_in is None:
            r_in = jnp.zeros((x.shape[0] * x.shape[1],
                              cfg.router_hidden_size), jnp.float32)
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        x = join(0, x, ZayaCCA(cfg, name="self_attn")(
            norm("input_layernorm")(x)))
        out, r = ZayaMoE(cfg, self.index, name="mlp")(
            norm("post_attention_layernorm")(x), r_in)
        return join(2, x, out), r


class ZayaForCausalLM(nn.Module):
    config: ZayaConfig

    @nn.compact
    def __call__(self, batch, deterministic: bool = True):
        """Logits [B, T, V] in float32 (``batch``: ids or ``{"input_ids"}``)."""
        cfg = self.config
        input_ids = batch["input_ids"] if isinstance(batch, dict) else batch
        embed = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
            embedding_init=nn.initializers.normal(
                1.0 / math.sqrt(cfg.hidden_size)), name="embed_tokens")
        x, r = embed(input_ids), None
        for i in range(cfg.num_hidden_layers):
            x, r = ZayaLayer(cfg, i, name=f"layers_{i}")(x, r)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)
        return embed.attend(x).astype(jnp.float32)

    def forward_logits(self, input_ids):
        return self(input_ids)


__all__ = ["ZayaConfig", "ZayaForCausalLM"]
