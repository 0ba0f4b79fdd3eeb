"""AI21 Jamba (``model_type: jamba``) in flax.linen.

The family is here for one mechanism: most of its layers carry no attention
at all. A layer's mixer is a Mamba-1 selective state-space block, except
every ``attn_layer_period``-th layer (from ``attn_layer_offset``), which is
causal attention with NO position embedding of any kind; every layer's
feed-forward is the dense SwiGLU (``num_experts: 1``). The serving path is
``inference/v2`` through ``adapt_jamba`` (``adapters/jamba.py``), where a
Mamba layer keeps a fixed-size state per sequence beside the paged keys and
values of the attention layers; this module gives the parameter tree
(``init``) and a plain dense forward.

Layer equations (``chipbench/reference/jamba_ref.py`` states them once more,
in float32): ``x = x + mixer(input_layernorm(x))``;
``x = x + swiglu(pre_ff_layernorm(x))``; a final RMSNorm and the head, tied
to the embedding. The Mamba mixer on ``u`` per token ``t``, with
``E = mamba_expand * hidden_size``, ``N = mamba_d_state``,
``R = mamba_dt_rank``, ``K = mamba_d_conv``:

- ``[a_t, z_t] = in_proj(u_t)`` (``[d, 2E]``, no bias);
- ``c_t = silu(conv_bias + sum_j conv_w[:, j] * a_{t-K+1+j})`` (depthwise,
  causal, zeros before the sequence's start);
- ``[r_t, B_t, C_t] = x_proj(c_t)`` (widths ``R, N, N``), each through an
  RMSNorm of its own (``dt_layernorm``, ``b_layernorm``, ``c_layernorm``) —
  Jamba's addition to Mamba-1;
- ``dt_t = softplus(dt_proj(r_t))`` (with bias); ``A = -exp(A_log)``;
- ``h_t = exp(dt_t[:, None] * A) * h_{t-1} + (dt_t * c_t)[:, None] * B_t``;
  ``y_t = h_t C_t + D * c_t``; ``out_t = out_proj(y_t * silu(z_t))``.

The recurrence (``dt``, ``exp(dt A)``, ``h``, ``y``) runs in float32 whatever
``dtype`` is: the published config sets ``use_mamba_kernels``, and that
kernel does.

Initialisation of what ``normal`` would make degenerate follows Mamba's
published one: ``A_log = log(1..N)`` in every channel, ``D = 1``, the
``dt_proj`` bias the inverse softplus of a log-uniform draw in
``[1e-3, 1e-1]``, norm gains 1 — so channels remember over different spans.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.llama import RMSNorm

MAMBA, ATTENTION = "mamba", "attention"


@dataclass
class JambaConfig:
    """The published ``config.json`` keys under their own names."""
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    expert_layer_period: int = 2
    expert_layer_offset: int = 1
    num_experts: int = 1
    num_experts_per_tok: int = 1
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    use_mamba_kernels: bool = True
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    sliding_window: Optional[int] = None
    tie_word_embeddings: bool = True
    hidden_act: str = "silu"
    # Mamba's published initialisation of the step size (dt_min, dt_max)
    mamba_dt_init_range: Tuple[float, float] = (1e-3, 1e-1)
    dtype: Any = jnp.float32
    family: str = "jamba"

    def __post_init__(self):
        if self.num_experts != 1:
            raise ValueError("num_experts != 1: this family's MoE layers are "
                             "not built (Jamba2-3B publishes 1)")
        if self.mamba_proj_bias or not self.mamba_conv_bias:
            raise ValueError("mamba_proj_bias / no mamba_conv_bias: not built")
        if self.sliding_window is not None:
            raise ValueError("sliding_window: the family publishes null")
        if not self.tie_word_embeddings or self.hidden_act != "silu":
            raise ValueError("an untied head or another activation than "
                             "silu: not built")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size is not a multiple of the heads")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def layer_types(self) -> Tuple[str, ...]:
        """As the ``jamba`` model type builds them from period and offset."""
        return tuple(
            ATTENTION if i % self.attn_layer_period == self.attn_layer_offset
            else MAMBA for i in range(self.num_hidden_layers))

    @classmethod
    def jamba2_3b(cls, **kw):
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw):
        """Both kinds of layer at toy widths: Mamba, attention, Mamba x2."""
        d = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                 num_hidden_layers=4, num_attention_heads=4,
                 num_key_value_heads=1, attn_layer_period=4,
                 attn_layer_offset=1, mamba_d_state=16, mamba_dt_rank=8,
                 max_position_embeddings=512)
        d.update(kw)
        return cls(**d)


def _a_log_init(key, shape, dtype=jnp.float32):
    E, N = shape
    return jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)),
                            (E, N)).astype(dtype)


def _dt_bias_init(lo: float, hi: float):
    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (math.log(hi) - math.log(lo)) + math.log(lo))
        dt = jnp.maximum(dt, 1e-4)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)  # softplus^-1
    return init


def selective_scan(dt, c, Bm, Cm, A, D):
    """The recurrence token by token, in float32: ``dt``, ``c`` ``[T, E]``,
    ``Bm``, ``Cm`` ``[T, N]``, ``A`` ``[E, N]``, ``D`` ``[E]`` -> ``y [T, E]``
    from a zero state."""
    def step(h, row):
        dt_t, c_t, b_t, c_out = row
        h = jnp.exp(dt_t[:, None] * A) * h + (dt_t * c_t)[:, None] * b_t[None]
        return h, h @ c_out + D * c_t
    h0 = jnp.zeros(A.shape, jnp.float32)
    return jax.lax.scan(step, h0, (dt, c, Bm, Cm))[1]


class JambaMamba(nn.Module):
    config: JambaConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        B, T, _ = u.shape
        E, N, R, K = (cfg.mamba_d_inner, cfg.mamba_d_state,
                      cfg.mamba_dt_rank, cfg.mamba_d_conv)
        dense = lambda feats, name, **kw: nn.Dense(
            feats, use_bias=False, dtype=cfg.dtype, name=name, **kw)
        az = dense(2 * E, "in_proj")(u)
        a, z = az[..., :E], az[..., E:]
        w = self.param("conv_weight", nn.initializers.normal((3 * K) ** -0.5), (E, K),
                       cfg.dtype)
        b = self.param("conv_bias", nn.initializers.zeros, (E,), cfg.dtype)
        pad = jnp.pad(a, ((0, 0), (K - 1, 0), (0, 0)))
        conv = sum(pad[:, j:j + T] * w[:, j] for j in range(K)) + b
        c = nn.silu(conv.astype(jnp.float32)).astype(cfg.dtype)
        rbc = dense(R + 2 * N, "x_proj")(c)
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        r = norm("dt_layernorm")(rbc[..., :R])
        Bm = norm("b_layernorm")(rbc[..., R:R + N])
        Cm = norm("c_layernorm")(rbc[..., R + N:])
        dt_bias = self.param("dt_bias",
                             _dt_bias_init(*cfg.mamba_dt_init_range), (E,),
                             jnp.float32)
        dt = jax.nn.softplus(dense(E, "dt_proj")(r).astype(jnp.float32)
                             + dt_bias)
        A = -jnp.exp(self.param("A_log", _a_log_init, (E, N), jnp.float32))
        D = self.param("D", nn.initializers.ones, (E,), jnp.float32)
        f32 = lambda v: v.astype(jnp.float32)
        y = jax.vmap(selective_scan, in_axes=(0, 0, 0, 0, None, None))(
            dt, f32(c), f32(Bm), f32(Cm), A, D)
        y = (y * nn.silu(f32(z))).astype(cfg.dtype)
        return dense(cfg.hidden_size, "out_proj")(y)


class JambaAttention(nn.Module):
    config: JambaConfig

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
            / (D ** 0.5)
        causal = jnp.tril(jnp.ones((T, T), bool))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        out = jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(cfg.dtype), v)
        return dense(cfg.hidden_size, "o_proj")(out.reshape(B, T, H * D))


class JambaMLP(nn.Module):
    config: JambaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dense = lambda feats, name: nn.Dense(feats, use_bias=False,
                                             dtype=cfg.dtype, name=name)
        return dense(cfg.hidden_size, "down_proj")(
            nn.silu(dense(cfg.intermediate_size, "gate_proj")(x))
            * dense(cfg.intermediate_size, "up_proj")(x))


class JambaBlock(nn.Module):
    config: JambaConfig
    index: int

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        u = norm("input_layernorm")(x)
        if cfg.layer_types[self.index] == MAMBA:
            x = x + JambaMamba(cfg, name="mamba")(u)
        else:
            x = x + JambaAttention(cfg, name="self_attn")(u)
        return x + JambaMLP(cfg, name="feed_forward")(
            norm("pre_ff_layernorm")(x))


class JambaForCausalLM(nn.Module):
    config: JambaConfig

    @nn.compact
    def __call__(self, batch, deterministic: bool = True):
        """Logits [B, T, V] in float32 (``batch``: ids or ``{"input_ids"}``)."""
        cfg = self.config
        input_ids = batch["input_ids"] if isinstance(batch, dict) else batch
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         name="embed_tokens")
        x = embed(input_ids)
        for i in range(cfg.num_hidden_layers):
            x = JambaBlock(cfg, i, name=f"layers_{i}")(x)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="final_layernorm")(x)
        return embed.attend(x.astype(jnp.float32)).astype(jnp.float32)

    def forward_logits(self, input_ids):
        return self(input_ids)


__all__ = ["JambaConfig", "JambaForCausalLM"]
