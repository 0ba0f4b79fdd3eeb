"""Arcee Trinity (``model_type: afmoe``) in flax.linen.

The family mixes three kinds of layer in one decoder, which is why it is
here: sliding-window layers that rotate positions beside full-attention
layers that carry NO position embedding, a few leading dense SwiGLU layers
before the sparse ones, and a sigmoid-scored router over many small experts
with a selection bias, sum-normalised weights, a scale and an always-on
shared expert. The serving path is ``inference/v2`` through ``adapt_afmoe``
(``adapters/afmoe.py``); this module gives the parameter tree (``init``) and a
plain dense forward the tests hold the engine and the benchmark's reference
to.

Layer equations, as the modelling code published with the checkpoints has
them (``chipbench/reference/afmoe_ref.py`` states them once more, in float32):

- ``x = embed(ids) * sqrt(hidden)`` when ``mup_enabled``;
- ``x = x + post_attention_layernorm(attn(input_layernorm(x)))``;
  ``x = x + post_mlp_layernorm(ffn(pre_mlp_layernorm(x)))``;
- attention: bias-free q/k/v, RMSNorm over each head's ``head_dim`` values of
  q and k, rotary embedding on ``sliding_attention`` layers only, causal GQA
  (sliding layers see ``i - window < j <= i``), then
  ``o_proj(out * sigmoid(gate_proj(x)))`` with ``x`` the normed layer input;
- the first ``num_dense_layers`` layers: SwiGLU of ``intermediate_size``; the
  others: ``scores = sigmoid(x W_r)`` in float32, the ``num_experts_per_tok``
  largest of ``scores + expert_bias`` chosen, weighed by their ``scores``
  (no bias) over their sum, times ``route_scale``; plus the shared expert's
  SwiGLU of the same input, unweighted.

Rotation pairs ``(x[2i], x[2i+1])`` like the rest of the zoo
(``models/llama.apply_rope``); the published code pairs ``(x[i], x[i+D/2])``,
which is the same function after a fixed permutation of each head's q/k
columns (and norm gains) at conversion.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.llama import RMSNorm, _window_bias, apply_rope

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclass
class AfmoeConfig:
    """The published ``config.json`` keys under their own names."""
    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144          # the leading dense layers' SwiGLU
    moe_intermediate_size: int = 1024      # one expert's (and the shared one's)
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    max_position_embeddings: int = 131072
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    sliding_window: int = 2048
    global_attn_every_n_layers: int = 4
    layer_types: Optional[Tuple[str, ...]] = None   # None: from the line above
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.826
    mup_enabled: bool = True
    # expert_bias is a buffer the published training moves to balance load;
    # random weights draw it with a spread that changes selections, so that a
    # router weighing with the biased scores cannot pass for the right one
    expert_bias_init_std: float = 0.05
    dtype: Any = jnp.float32
    family: str = "afmoe"

    def __post_init__(self):
        if self.layer_types is None:
            n = self.global_attn_every_n_layers
            self.layer_types = tuple(
                FULL if (i + 1) % n == 0 else SLIDING
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types has {len(self.layer_types)} entries for "
                f"num_hidden_layers={self.num_hidden_layers}")
        unknown = set(self.layer_types) - {SLIDING, FULL}
        if unknown:
            raise ValueError(f"unknown layer_types {sorted(unknown)}")
        if self.score_func != "sigmoid":
            raise ValueError(f"score_func {self.score_func!r}: the family "
                             "publishes 'sigmoid' only")

    def is_moe_layer(self, i: int) -> bool:
        return i >= self.num_dense_layers

    @classmethod
    def trinity_mini(cls, **kw):
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw):
        """All three layer kinds at toy widths: dense+sliding, MoE+sliding,
        MoE+full."""
        d = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                 moe_intermediate_size=32, num_hidden_layers=4,
                 num_dense_layers=1, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=16,
                 max_position_embeddings=256, sliding_window=8,
                 global_attn_every_n_layers=4, num_experts=8,
                 num_experts_per_tok=2)
        d.update(kw)
        return cls(**d)


def route(scores_in: jax.Array, expert_bias: jax.Array, cfg: AfmoeConfig):
    """Router logits [N, E] (float32) -> (weights [N, k], expert ids [N, k])."""
    scores = jax.nn.sigmoid(scores_in.astype(jnp.float32))
    _, ids = jax.lax.top_k(scores + expert_bias.astype(jnp.float32),
                           cfg.num_experts_per_tok)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    if cfg.route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * cfg.route_scale, ids


class AfmoeMLP(nn.Module):
    config: AfmoeConfig
    width: int

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dense = lambda feats, name: nn.Dense(feats, use_bias=False,
                                             dtype=cfg.dtype, name=name)
        return dense(cfg.hidden_size, "down_proj")(
            nn.silu(dense(self.width, "gate_proj")(x))
            * dense(self.width, "up_proj")(x))


class AfmoeMoE(nn.Module):
    """Routed experts (stacked ``[E, K, N]``, as Mixtral's are) plus the
    shared expert. The dense forward evaluates the chosen experts through the
    grouped GEMM, sorted by expert."""

    config: AfmoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, T, C = x.shape
        E, k, F = cfg.num_experts, cfg.num_experts_per_tok, \
            cfg.moe_intermediate_size
        tokens = x.reshape(B * T, C)
        logits = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                          name="router")(tokens.astype(jnp.float32))
        bias = self.param("expert_bias",
                          nn.initializers.normal(cfg.expert_bias_init_std),
                          (E,), jnp.float32)
        # each expert's matrices as nn.Dense would draw them (the shared
        # expert's are Dense), so routed and shared parts weigh alike
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=(0,))
        w_gate = self.param("w_gate", init, (E, C, F), cfg.dtype)
        w_up = self.param("w_up", init, (E, C, F), cfg.dtype)
        w_down = self.param("w_down", init, (E, F, C), cfg.dtype)
        weights, ids = route(logits, bias, cfg)

        flat_e = ids.reshape(-1)
        order = jnp.argsort(flat_e)
        rows = tokens[jnp.repeat(jnp.arange(B * T), k)[order]]
        sizes = jnp.bincount(flat_e, length=E).astype(jnp.int32)
        h = nn.silu(jax.lax.ragged_dot(rows, w_gate, sizes)) \
            * jax.lax.ragged_dot(rows, w_up, sizes)
        ys = jax.lax.ragged_dot(h, w_down, sizes)
        ys = ys * weights.reshape(-1)[order][:, None].astype(ys.dtype)
        out = ys[jnp.argsort(order)].reshape(B * T, k, C).sum(axis=1)
        if cfg.num_shared_experts:
            out = out + AfmoeMLP(cfg, F * cfg.num_shared_experts,
                                 name="shared_experts")(tokens)
        return out.reshape(B, T, C)


class AfmoeAttention(nn.Module):
    config: AfmoeConfig
    sliding: bool

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
        gate = dense(H * D, "gate_proj")(x)
        if self.sliding:        # full layers carry no position embedding
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        bias = _window_bias(positions, positions,
                            cfg.sliding_window if self.sliding else None)
        qg = q.reshape(B, T, Hkv, H // Hkv, D)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k).astype(jnp.float32) \
            / (D ** 0.5)
        p = jax.nn.softmax(s + bias[:, :, None], axis=-1).astype(cfg.dtype)
        out = jnp.einsum("bgrqk,bkgd->bqgrd", p, v).reshape(B, T, H * D)
        out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(cfg.dtype)
        return dense(cfg.hidden_size, "o_proj")(out)


class AfmoeBlock(nn.Module):
    config: AfmoeConfig
    index: int

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)
        a = AfmoeAttention(cfg, cfg.layer_types[self.index] == SLIDING,
                           name="self_attn")(norm("input_layernorm")(x),
                                             positions)
        x = x + norm("post_attention_layernorm")(a)
        h = norm("pre_mlp_layernorm")(x)
        if cfg.is_moe_layer(self.index):
            m = AfmoeMoE(cfg, name="mlp")(h)
        else:
            m = AfmoeMLP(cfg, cfg.intermediate_size, name="mlp")(h)
        return x + norm("post_mlp_layernorm")(m)


class AfmoeForCausalLM(nn.Module):
    config: AfmoeConfig

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
        if cfg.mup_enabled:
            x = (x.astype(jnp.float32) * cfg.hidden_size ** 0.5
                 ).astype(cfg.dtype)
        for i in range(cfg.num_hidden_layers):
            x = AfmoeBlock(cfg, i, name=f"layers_{i}")(x, positions)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                        name="lm_head")(x).astype(jnp.float32)

    def forward_logits(self, input_ids, positions=None):
        return self(input_ids, positions=positions)


__all__ = ["AfmoeConfig", "AfmoeForCausalLM"]
