"""IBM Granite 4.0-H (``model_type: granitemoehybrid``) in flax.linen.

The family is here for its mixer: most layers are **Mamba-2** (SSD) blocks
whose state is a matrix per head, beside a few layers of grouped-query
attention with NO position term of any kind; every layer's feed-forward is a
mixture of routed experts plus a shared SwiGLU. The serving path is
``inference/v2`` through ``adapt_granite`` (``adapters/granite``); this module
gives the parameter tree (``init``) and a plain dense forward.

Layer equations (``chipbench/reference/granite_ref.py`` states them once more,
in float32). ``x = embedding_multiplier * embed[ids]``; per layer, with ``r =
residual_multiplier``: ``x += r * mixer(input_layernorm(x))``; ``h2 =
post_attention_layernorm(x)``; ``x += r * (moe(h2) + shared(h2))``; then
``logits = norm(x) embed^T / logits_scaling`` (tied head).

- attention: ``q, k, v`` without bias and without rotation; causal softmax of
  ``q k^T * attention_multiplier`` (1/128 as published, not ``head_dim **
  -0.5``), grouped queries, ``o_proj``;
- Mamba-2 on ``u``, ``H = mamba_n_heads`` heads of ``P = mamba_d_head``,
  ``E = H P``, ``N = mamba_d_state``, ``G = mamba_n_groups`` pairs of B and C
  (published: one): ``[z | xBC | dt] = in_proj(u)`` (widths ``E``, ``E + 2 G
  N``, ``H``); ``xBC = silu(conv1d(xBC) +
  b)`` depthwise and causal over ``mamba_d_conv`` taps; ``dt = softplus(dt +
  dt_bias)``; ``a = -exp(A_log)`` a head; ``S_t[h] = exp(dt_t[h] a[h])
  S_{t-1}[h] + dt_t[h] x_t[h] (outer) B_t``; ``y_t[h] = S_t[h] C_t + D[h]
  x_t[h]``; ``g = y * silu(z)``; ``n = g * rsqrt(mean(g^2) + eps) * norm``
  (the gate first, then the norm, over each group's ``E / G`` channels);
  ``out_proj(n)``. The
  recurrence runs in float32 whatever ``dtype`` is;
- MoE: router logits in float32, the ``num_experts_per_tok`` largest, softmax
  over those; experts are SwiGLUs of width ``intermediate_size``, stored as
  the repo's ``w_gate``/``w_up``/``w_down`` stacks (the published checkpoint
  fuses gate and up into one ``[hidden, 2 * width]`` matrix: its first half
  is ``w_gate``, its second ``w_up``); the shared SwiGLU of width
  ``shared_intermediate_size`` sees every token, unweighted.

``experts_held = (first, count)``: this module's expert stacks hold only
experts ``first .. first + count - 1`` of the ``num_local_experts`` the router
scores (one chip's share under expert parallelism); what the absent ones
would add is left out.

Initialisation of what ``normal`` would make degenerate follows Mamba-2's
published one: ``A_log = log(U[1, 16])`` a head, ``D = 1``, ``dt_bias`` the
inverse softplus of a log-uniform draw in ``[1e-3, 1e-1]``, norm gains 1.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.jamba import _dt_bias_init
from deepspeed_tpu.models.llama import RMSNorm

MAMBA, ATTENTION = "mamba", "attention"


@dataclass
class GraniteConfig:
    """The published ``config.json`` keys under their own names."""
    vocab_size: int = 100352
    hidden_size: int = 4096
    intermediate_size: int = 768            # one routed expert's width
    shared_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    layer_types: Optional[Tuple[str, ...]] = None   # None: period 10, at 5
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_local_experts: int = 72
    num_experts_per_tok: int = 10
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    attention_bias: bool = False
    attention_multiplier: float = 0.0078125
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    position_embedding_type: str = "nope"
    normalization_function: str = "rmsnorm"
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    hidden_act: str = "silu"
    # (first, count) of the routed experts this model holds; None: all
    experts_held: Optional[Tuple[int, int]] = None
    mamba_dt_init_range: Tuple[float, float] = (1e-3, 1e-1)
    dtype: Any = jnp.float32
    family: str = "granite"

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = tuple(
                ATTENTION if i % 10 == 5 else MAMBA
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers or set(
                self.layer_types) - {MAMBA, ATTENTION}:
            raise ValueError("layer_types needs one of 'mamba'/'attention' "
                             "for each of num_hidden_layers")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError("mamba_n_heads is not a multiple of "
                             "mamba_n_groups")
        if self.mamba_n_heads * self.mamba_d_head \
                != self.mamba_expand * self.hidden_size:
            raise ValueError("mamba_n_heads x mamba_d_head is not "
                             "mamba_expand x hidden_size")
        if self.mamba_proj_bias or not self.mamba_conv_bias \
                or self.attention_bias:
            raise ValueError("mamba_proj_bias / attention_bias / no "
                             "mamba_conv_bias: not built")
        if self.position_embedding_type != "nope":
            raise ValueError("a position embedding: the family publishes "
                             "'nope'")
        if not self.tie_word_embeddings or self.hidden_act != "silu" \
                or self.normalization_function != "rmsnorm":
            raise ValueError("an untied head, another activation than silu "
                             "or another norm than rmsnorm: not built")
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.num_local_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"span of the {self.num_local_experts} experts")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_local_experts)

    @classmethod
    def granite_4_0_h_small(cls, **kw):
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw):
        """Both kinds of layer at toy widths (the state's ``N`` and the
        head size as published, so the kernels are the real ones)."""
        d = dict(vocab_size=256, hidden_size=128, intermediate_size=64,
                 shared_intermediate_size=128, num_hidden_layers=4,
                 layer_types=(MAMBA, ATTENTION, MAMBA, MAMBA),
                 num_attention_heads=4, num_key_value_heads=2,
                 num_local_experts=8, num_experts_per_tok=3,
                 mamba_n_heads=4, mamba_d_head=64, mamba_d_state=128,
                 attention_multiplier=0.015625, max_position_embeddings=512)
        d.update(kw)
        return cls(**d)


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                   ).astype(dtype)


def ssd_recurrence(dt, x, Bm, Cm, a, D):
    """The recurrence token by token, in float32, from a zero state: ``dt``
    ``[T, H]``, ``x`` ``[T, H, P]``, ``Bm``, ``Cm`` ``[T, N]`` (one group) or
    ``[T, G, N]`` (head ``h`` reads group ``h // (H / G)``), ``a``, ``D``
    ``[H]`` -> ``y`` ``[T, H, P]``."""
    H = x.shape[1]
    if Bm.ndim == 2:
        Bm, Cm = Bm[:, None], Cm[:, None]
    per_head = lambda v: jnp.repeat(v, H // v.shape[0], axis=0)     # [H, N]

    def step(S, row):
        dt_t, x_t, b_t, c_t = row
        S = jnp.exp(dt_t * a)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * per_head(b_t)[:, None, :]
        return S, jnp.einsum("hpn,hn->hp", S, per_head(c_t)) \
            + D[:, None] * x_t
    S0 = jnp.zeros(x.shape[1:] + Bm.shape[2:], jnp.float32)
    return jax.lax.scan(step, S0, (dt, x, Bm, Cm))[1]


class GraniteMamba(nn.Module):
    config: GraniteConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        B, T, _ = u.shape
        H, P, N, K, G = (cfg.mamba_n_heads, cfg.mamba_d_head,
                         cfg.mamba_d_state, cfg.mamba_d_conv,
                         cfg.mamba_n_groups)
        E, W = H * P, H * P + 2 * G * N
        dense = lambda feats, name: nn.Dense(feats, use_bias=False,
                                             dtype=cfg.dtype, name=name)
        zxd = dense(E + W + H, "in_proj")(u)
        z, a, dt = zxd[..., :E], zxd[..., E:E + W], zxd[..., E + W:]
        w = self.param("conv_weight",
                       nn.initializers.normal((3 * K) ** -0.5), (W, K),
                       cfg.dtype)
        b = self.param("conv_bias", nn.initializers.zeros, (W,), cfg.dtype)
        pad = jnp.pad(a, ((0, 0), (K - 1, 0), (0, 0)))
        conv = sum(pad[:, j:j + T] * w[:, j] for j in range(K)) + b
        c = nn.silu(conv.astype(jnp.float32)).astype(cfg.dtype)
        f32 = lambda v: v.astype(jnp.float32)
        dt_bias = self.param("dt_bias",
                             _dt_bias_init(*cfg.mamba_dt_init_range), (H,),
                             jnp.float32)
        dt = jax.nn.softplus(f32(dt) + dt_bias)
        a_neg = -jnp.exp(self.param("A_log", _a_log_init, (H,), jnp.float32))
        D = self.param("D", nn.initializers.ones, (H,), jnp.float32)
        y = jax.vmap(ssd_recurrence, in_axes=(0, 0, 0, 0, None, None))(
            dt, f32(c[..., :E]).reshape(B, T, H, P),
            f32(c[..., E:E + G * N]).reshape(B, T, G, N),
            f32(c[..., E + G * N:]).reshape(B, T, G, N), a_neg,
            D).reshape(B, T, E)
        g = (y * nn.silu(f32(z))).reshape(B, T, G, E // G)
        gain = self.param("norm", nn.initializers.ones, (E,), cfg.dtype)
        n = (g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                               + cfg.rms_norm_eps)).reshape(B, T, E) \
            * f32(gain)
        return dense(cfg.hidden_size, "out_proj")(n.astype(cfg.dtype))


class GraniteAttention(nn.Module):
    config: GraniteConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, T, _ = x.shape
        H, Hkv, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        dense = lambda feats, name: nn.Dense(feats, use_bias=False,
                                             dtype=cfg.dtype, name=name)
        q = dense(H * D, "q_proj")(x).reshape(B, T, Hkv, H // Hkv, D)
        k = dense(Hkv * D, "k_proj")(x).reshape(B, T, Hkv, D)
        v = dense(Hkv * D, "v_proj")(x).reshape(B, T, Hkv, D)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", q, k).astype(jnp.float32) \
            * cfg.attention_multiplier
        causal = jnp.tril(jnp.ones((T, T), bool))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        out = jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(cfg.dtype), v)
        return dense(cfg.hidden_size, "o_proj")(out.reshape(B, T, H * D))


class GraniteMLP(nn.Module):
    config: GraniteConfig
    width: int

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dense = lambda feats, name: nn.Dense(feats, use_bias=False,
                                             dtype=cfg.dtype, name=name)
        return dense(cfg.hidden_size, "down_proj")(
            nn.silu(dense(self.width, "gate_proj")(x))
            * dense(self.width, "up_proj")(x))


class GraniteMoE(nn.Module):
    """The held routed experts (stacked ``[count, K, N]``) plus the shared
    MLP. The dense forward weighs every held expert for every token (0 where
    not chosen): the same sum as a dispatch, at test sizes."""

    config: GraniteConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, T, C = x.shape
        E, F = cfg.num_local_experts, cfg.intermediate_size
        first, count = cfg.held
        tokens = x.reshape(B * T, C)
        logits = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                          name="router")(tokens.astype(jnp.float32))
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=(0,))
        w_gate = self.param("w_gate", init, (count, C, F), cfg.dtype)
        w_up = self.param("w_up", init, (count, C, F), cfg.dtype)
        w_down = self.param("w_down", init, (count, F, C), cfg.dtype)
        top, ids = jax.lax.top_k(logits, cfg.num_experts_per_tok)
        dense = jnp.sum(jax.nn.one_hot(ids, E, dtype=jnp.float32)
                        * jax.nn.softmax(top, axis=-1)[..., None],
                        axis=1)[:, first:first + count]

        def add_expert(acc, args):
            wg, wu, wd, wt = args
            y = (nn.silu(tokens @ wg) * (tokens @ wu)) @ wd
            return acc + y.astype(jnp.float32) * wt[:, None], None

        out, _ = jax.lax.scan(add_expert,
                              jnp.zeros(tokens.shape, jnp.float32),
                              (w_gate, w_up, w_down, dense.T))
        out = out.astype(cfg.dtype) + GraniteMLP(
            cfg, cfg.shared_intermediate_size, name="shared_mlp")(tokens)
        return out.reshape(B, T, C)


class GraniteBlock(nn.Module):
    config: GraniteConfig
    index: int

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        r = cfg.residual_multiplier
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        u = norm("input_layernorm")(x)
        if cfg.layer_types[self.index] == MAMBA:
            x = x + r * GraniteMamba(cfg, name="mamba")(u)
        else:
            x = x + r * GraniteAttention(cfg, name="self_attn")(u)
        return x + r * GraniteMoE(cfg, name="block_sparse_moe")(
            norm("post_attention_layernorm")(x))


class GraniteForCausalLM(nn.Module):
    config: GraniteConfig

    @nn.compact
    def __call__(self, batch, deterministic: bool = True):
        """Logits [B, T, V] in float32 (``batch``: ids or ``{"input_ids"}``)."""
        cfg = self.config
        input_ids = batch["input_ids"] if isinstance(batch, dict) else batch
        embed = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
            embedding_init=nn.initializers.normal(
                1.0 / math.sqrt(cfg.hidden_size)), name="embed_tokens")
        x = embed(input_ids) * jnp.asarray(cfg.embedding_multiplier,
                                           cfg.dtype)
        for i in range(cfg.num_hidden_layers):
            x = GraniteBlock(cfg, i, name=f"layers_{i}")(x)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)
        return (embed.attend(x.astype(jnp.float32)).astype(jnp.float32)
                / cfg.logits_scaling)

    def forward_logits(self, input_ids):
        return self(input_ids)


__all__ = ["GraniteConfig", "GraniteForCausalLM"]
