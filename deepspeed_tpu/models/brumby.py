"""Manifest AI Brumby (``model_type: brumby``; Brumby-14B-Base) in flax.linen.

The family is here for one mechanism: NO layer attends over cached keys.
The block is Qwen3-14B's (RMSNorm, 40 query heads over 8 KV heads of 128
with a norm on q and k and the whole head rotated, a dense SwiGLU MLP, an
untied head) with its attention replaced by **power retention** (Buckman,
Gelada, Zhang, *Scaling Context Requires Rethinking Attention*, 2025, and
Manifest AI's release note for Brumby-14B-Base, 2025-10): linear attention
whose feature map is the degree-``p`` tensor power of the key, so that a
layer's memory of a sequence is a state of fixed size. The serving path is
``inference/v2`` through ``adapt_brumby`` (``adapters/brumby.py``), where a
layer keeps a slot of the state pool a sequence and the model holds no
pages; this module gives the parameter tree (``init``) in the published
layout and a plain dense forward in the attention form.

Layer equations (``chipbench/reference/brumby_ref.py`` states them once
more, in float32): ``x = x + PR(input_layernorm(x))``; ``x = x +
swiglu(post_attention_layernorm(x))``; a final RMSNorm and the head. ``PR``
on the rows ``u_t`` of one sequence, ``d = head_dim``, query head ``j`` of KV
head ``i = j // (Hq / Hk)``::

    q_t = rope_t(q_norm(q_proj u_t))    k_t = rope_t(k_norm(k_proj u_t))
    v_t = v_proj u_t                    lg_t = log_sigmoid(g_proj u_t + g_bias)
    c_t = sum_{s <= t} lg_s                                  (a KV head, float32)
    w[t, s] = ((q_t . k_s) / sqrt(d))^p exp(c_t - c_s)       (s <= t)
    y_t     = sum_s w[t, s] v_s / (sum_s w[t, s] + eps)
    PR(u)_t = o_proj concat_j y_{t, j}

What the published ``config.json`` does not pin is ASSUMED here, each in one
place (the fields below the published keys): the power ``p = 2``, the gate
(one a KV head a token, from a linear map of the layer's normed input with a
bias), the normaliser and its ``eps``, the ``1 / sqrt(d)``, the chunk of the
serving path's scan. Norms and rotation are Qwen3's: an RMSNorm over each
head's ``d`` values (a gain a head width), the whole head rotated, value
``i`` paired with ``i + d / 2``.

Initialisation of what ``normal`` would make degenerate: the gate's weight
is small and its bias is drawn so that ``g`` lies in ``gate_init[i % 2]`` for
KV head ``i`` — slow heads (0.98-0.9999: a token still weighs hundreds of
positions on) beside fast ones (0.5-0.9) — so that a state carried wrongly
shows in the output.
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
class BrumbyConfig:
    """The published ``config.json`` keys under their own names (the
    defaults are Brumby-14B-Base's), then what is assumed."""
    vocab_size: int = 151936
    hidden_size: int = 5120
    intermediate_size: int = 17408
    num_hidden_layers: int = 40
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    hidden_act: str = "silu"
    attention_bias: bool = False
    max_position_embeddings: int = 32768
    max_window_layers: int = 40
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    rope_scaling: Optional[dict] = None
    sliding_window: Optional[int] = None
    use_sliding_window: bool = False
    tie_word_embeddings: bool = False
    model_type: str = "brumby"
    # -- assumed (the module's docstring) --
    power: int = 2
    retention_eps: float = 1e-6
    chunk_size: int = 128
    # the range of g a KV head's bias is drawn for: even heads, odd heads
    gate_init: Tuple[Tuple[float, float], Tuple[float, float]] = (
        (0.98, 0.9999), (0.5, 0.9))
    dtype: Any = jnp.float32
    family: str = "brumby"

    def __post_init__(self):
        if self.power != 2:
            raise ValueError("power != 2: the state's expansion is built for "
                             "the symmetric square only")
        if self.attention_bias or self.rope_scaling or self.use_sliding_window \
                or self.sliding_window is not None or self.tie_word_embeddings \
                or self.hidden_act != "silu":
            raise ValueError("projection biases, rope scaling, a sliding "
                             "window, a tied head or another activation than "
                             "silu: the family publishes none, not built")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.head_dim % 2:
            raise ValueError("query heads are not a multiple of the KV heads, "
                             "or the head size is odd")

    @classmethod
    def brumby_14b_base(cls, **kw):
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw):
        """Two layers at toy widths: 4 query heads over 2 KV heads of 16."""
        d = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                 num_hidden_layers=2, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=16, max_window_layers=2,
                 max_position_embeddings=512, rope_theta=10000.0,
                 chunk_size=8)
        d.update(kw)
        return cls(**d)


def _dense(cfg, feats, name, **kw):
    return nn.Dense(feats, use_bias=False, dtype=cfg.dtype, name=name, **kw)


def gate_bias_init(ranges):
    """A KV head's bias: the logit of a gate drawn uniformly in ``ranges[i %
    2]`` for head ``i``."""
    def init(key, shape, dtype=jnp.float32):
        lo, hi = (jnp.asarray([r[n] for r in ranges], jnp.float32)[
            jnp.arange(shape[0]) % 2] for n in (0, 1))
        g = lo + (hi - lo) * jax.random.uniform(key, shape, jnp.float32)
        return (jnp.log(g) - jnp.log1p(-g)).astype(dtype)
    return init


def retention(q, k, v, lg, eps: float):
    """The attention form on one sequence from an empty state, in float32:
    ``q`` ``[T, Hk, G, d]``, ``k``, ``v`` ``[T, Hk, d]``, ``lg`` ``[T, Hk]``
    -> ``y`` ``[T, Hk, G, d]``."""
    f32 = jnp.float32
    T, d = k.shape[0], k.shape[-1]
    c = jnp.cumsum(lg.astype(f32), axis=0).T                    # [Hk, T]
    s = jnp.einsum("thgd,shd->hgts", q.astype(f32), k.astype(f32),
                   precision=jax.lax.Precision.HIGHEST)
    seen = jnp.tril(jnp.ones((T, T), bool))
    decay = jnp.exp(jnp.where(seen, c[:, None, :, None] - c[:, None, None, :],
                              0.0))
    w = jnp.where(seen, s * s / d * decay, 0.0)
    num = jnp.einsum("hgts,shd->thgd", w, v.astype(f32),
                     precision=jax.lax.Precision.HIGHEST)
    return num / (jnp.transpose(w.sum(-1), (2, 0, 1))[..., None] + eps)


class BrumbyRetention(nn.Module):
    config: BrumbyConfig

    @nn.compact
    def __call__(self, u, positions):
        cfg = self.config
        B, T, _ = u.shape
        H, Hk, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        rot = lambda x: rope_half(x, positions, cfg.rope_theta, D)
        q = rot(norm("q_norm")(_dense(cfg, H * D, "q_proj")(u)
                               .reshape(B, T, H, D)))
        k = rot(norm("k_norm")(_dense(cfg, Hk * D, "k_proj")(u)
                               .reshape(B, T, Hk, D)))
        v = _dense(cfg, Hk * D, "v_proj")(u).reshape(B, T, Hk, D)
        gate = _dense(cfg, Hk, "g_proj", kernel_init=nn.initializers.normal(
            0.1 / math.sqrt(cfg.hidden_size)))(u)
        bias = self.param("g_bias", gate_bias_init(cfg.gate_init), (Hk,),
                          jnp.float32)
        lg = jax.nn.log_sigmoid(gate.astype(jnp.float32) + bias)
        y = jax.vmap(lambda q, k, v, lg: retention(
            q, k, v, lg, cfg.retention_eps))(
                q.reshape(B, T, Hk, H // Hk, D), k, v, lg)
        return _dense(cfg, cfg.hidden_size, "o_proj")(
            y.astype(cfg.dtype).reshape(B, T, H * D))


class BrumbyMLP(nn.Module):
    config: BrumbyConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        return _dense(cfg, cfg.hidden_size, "down_proj")(
            nn.silu(_dense(cfg, cfg.intermediate_size, "gate_proj")(x))
            * _dense(cfg, cfg.intermediate_size, "up_proj")(x))


class BrumbyLayer(nn.Module):
    config: BrumbyConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        x = x + BrumbyRetention(cfg, name="self_attn")(
            norm("input_layernorm")(x), positions)
        return x + BrumbyMLP(cfg, name="mlp")(
            norm("post_attention_layernorm")(x))


class BrumbyForCausalLM(nn.Module):
    config: BrumbyConfig

    @nn.compact
    def __call__(self, batch, deterministic: bool = True):
        """Logits [B, T, V] in float32 (``batch``: ids or ``{"input_ids"}``)."""
        cfg = self.config
        input_ids = batch["input_ids"] if isinstance(batch, dict) else batch
        positions = jnp.broadcast_to(jnp.arange(input_ids.shape[1]),
                                     input_ids.shape)
        x = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
            embedding_init=nn.initializers.normal(
                1.0 / math.sqrt(cfg.hidden_size)), name="embed_tokens")(
                    input_ids)
        for i in range(cfg.num_hidden_layers):
            x = BrumbyLayer(cfg, name=f"layers_{i}")(x, positions)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)
        return _dense(cfg, cfg.vocab_size, "lm_head")(x).astype(jnp.float32)

    def forward_logits(self, input_ids):
        return self(input_ids)


__all__ = ["BrumbyConfig", "BrumbyForCausalLM"]
