"""GPT-2 in flax.linen (BASELINE ladder config #1).

The reference has no in-repo GPT-2 (it trains HF/Megatron models through the
engine); this model zoo exists so the framework is runnable end-to-end standalone,
like the reference's ``simple_model.py`` test fixtures but production-shaped.
Design: pre-LN transformer, learned positions, causal attention routed through
``deepspeed_tpu.ops.attention`` (jnp today, Pallas flash-attention when available).

The module maps a batch (dict with ``input_ids`` [B, T] and optional ``labels``)
to the mean next-token cross-entropy — matching the engine convention that
``model.apply(params, batch)`` returns the loss.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from jax.ad_checkpoint import checkpoint_name

from deepspeed_tpu.ops.attention import dot_product_attention
from deepspeed_tpu.runtime.activation_checkpointing import apply_checkpointed_layers


@dataclass
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    mlp_ratio: int = 4
    dropout: float = 0.0
    eps: float = 1e-5        # HF GPT-2 layer_norm_epsilon
    dtype: Any = jnp.float32
    # activation checkpointing (parity: reference
    # runtime/activation_checkpointing/checkpointing.py; on TPU = jax.checkpoint
    # around each block, letting XLA re-materialise instead of storing activations)
    remat: bool = False
    remat_policy: Optional[str] = None
    # Ulysses sequence parallelism (parallel/ulysses.py): attention through
    # two all-to-alls on the 'seq' mesh axis; no-op when the mesh has no seq
    # axis. Requires n_head and T divisible by the seq axis size.
    sequence_parallel: bool = False
    # rows per chunk in the fused projection+CE loss (llama.py
    # chunked_causal_lm_loss). The head GEMM's M dim is chunk*(T-1): larger
    # chunks raise MXU efficiency, smaller bound the [chunk, T, V] transient
    # (logits and, under differentiation, their gradient: one iteration's).
    lm_loss_chunk: int = 4

    @classmethod
    def small(cls, **kw):
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw):
        """Test-sized config (analog of the reference's ``simple_model.py``
        fixtures)."""
        defaults = dict(vocab_size=256, n_positions=128, n_embd=64, n_layer=2, n_head=4)
        defaults.update(kw)
        return cls(**defaults)


class CausalSelfAttention(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        B, T, C = x.shape
        qkv = nn.Dense(3 * cfg.n_embd, dtype=cfg.dtype, name="c_attn")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        heads = lambda t: t.reshape(B, T, cfg.n_head, C // cfg.n_head)
        if cfg.sequence_parallel:
            from deepspeed_tpu.parallel.ulysses import sequence_parallel_attention
            out = sequence_parallel_attention(heads(q), heads(k), heads(v),
                                              causal=True)
        else:
            out = dot_product_attention(heads(q), heads(k), heads(v), causal=True)
        # tag for the selective remat policies ("attn_out_saveable"): saving
        # this [B, T, C] tensor lets backward skip recomputing the attention
        # kernel while everything else still rematerialises
        out = checkpoint_name(out.reshape(B, T, C), "attn_out")
        return nn.Dense(cfg.n_embd, dtype=cfg.dtype, name="c_proj")(out)


class MLP(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        h = nn.Dense(cfg.mlp_ratio * cfg.n_embd, dtype=cfg.dtype, name="c_fc")(x)
        h = nn.gelu(h)
        return nn.Dense(cfg.n_embd, dtype=cfg.dtype, name="c_proj")(h)


class Block(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        x = x + CausalSelfAttention(cfg, name="attn")(
            nn.LayerNorm(epsilon=cfg.eps, dtype=cfg.dtype, name="ln_1")(x), deterministic)
        x = x + MLP(cfg, name="mlp")(nn.LayerNorm(epsilon=cfg.eps, dtype=cfg.dtype, name="ln_2")(x))
        return x


class GPT2LMHead(nn.Module):
    """Returns loss when batch has labels (or from shifted input_ids), else logits."""

    config: GPT2Config

    def setup(self):
        cfg = self.config
        self.wte = nn.Embed(cfg.vocab_size, cfg.n_embd, dtype=cfg.dtype, name="wte")
        self.wpe = nn.Embed(cfg.n_positions, cfg.n_embd, dtype=cfg.dtype, name="wpe")
        self.blocks = [Block(cfg, name=f"h_{i}") for i in range(cfg.n_layer)]
        self.ln_f = nn.LayerNorm(epsilon=cfg.eps, dtype=cfg.dtype, name="ln_f")

    def __call__(self, batch, deterministic: bool = True):
        cfg = self.config
        if isinstance(batch, dict):
            input_ids = batch["input_ids"]
            labels = batch.get("labels")
        else:
            input_ids, labels = batch, None
        B, T = input_ids.shape
        x = self.wte(input_ids) + self.wpe(jnp.arange(T)[None, :])
        pld_theta = batch.get("pld_theta") if isinstance(batch, dict) else None
        if pld_theta is not None:
            # progressive layer drop (engine-injected; parity: PLD hook
            # engine.py:1812 + runtime/progressive_layer_drop.py): deeper
            # layers drop with higher probability, whole-batch Bernoulli.
            # Composed INSIDE the checkpointed layer application so remat
            # still bounds activation memory.
            from deepspeed_tpu.runtime.progressive_layer_drop import (
                apply_layer_drop, pld_keep_prob)
            theta0 = pld_theta[0]
            key0 = batch["pld_rng"][0]

            def call_layer(mdl, h, i):
                x_new = mdl.blocks[i](h, deterministic)
                return apply_layer_drop(x_new, h,
                                        pld_keep_prob(i, cfg.n_layer, theta0),
                                        jax.random.fold_in(key0, i))

            def post_layer(x_new, h, i):
                return apply_layer_drop(x_new, h,
                                        pld_keep_prob(i, cfg.n_layer, theta0),
                                        jax.random.fold_in(key0, i))
        else:
            call_layer = lambda mdl, h, i: mdl.blocks[i](h, deterministic)
            post_layer = None
        # the scheduled ZeRO-3 walk lifts blocks to pure apply calls, which
        # cannot thread flax dropout RNGs — only offer it when deterministic
        x = apply_checkpointed_layers(self, x, call_layer, cfg.n_layer,
                                      cfg.remat, cfg.remat_policy,
                                      layers=self.blocks if deterministic else None,
                                      layer_args=(deterministic,),
                                      post_layer=post_layer)
        x = self.ln_f(x)

        if labels is None and isinstance(batch, dict) and "input_ids" in batch:
            labels = input_ids  # LM objective: predict next token of the same ids
        if labels is None:
            return self.wte.attend(x.astype(jnp.float32))  # tied head, fp32 logits
        # fused chunked projection+CE: the [B, T, V] logits never materialise
        # (see models/llama.py chunked_causal_lm_loss)
        from deepspeed_tpu.models.llama import chunked_causal_lm_loss
        return chunked_causal_lm_loss(x, self.wte.embedding, labels,
                                      batch_chunk=cfg.lm_loss_chunk)
