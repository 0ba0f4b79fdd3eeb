"""Llama-family decoder models in flax.linen (Llama-2 / Mistral via config).

Parity role: the reference ships these families as *inference containers and
model implementations* (``module_inject/containers/llama.py``, ``llama2.py``,
``inference/v2/model_implementations/{llama_v2,mistral}``) over HF weights; this
framework is standalone, so the families live here as first-class flax models used
by both the training engine (BASELINE ladder config #3: Llama-2-7B ZeRO-3 bf16)
and the inference engines.

Architecture (Llama-2 / Mistral lineage): RMSNorm pre-norm, rotary position
embeddings, grouped-query attention (``num_key_value_heads < num_attention_heads``),
SwiGLU MLP, untied LM head, optional sliding-window attention (Mistral).

Two call paths:
  - ``__call__(batch)``: training convention — mean next-token cross-entropy
    (or logits when no labels can be formed), matching the engine contract.
  - ``decode(input_ids, cache, positions)``: incremental decoding with an explicit
    KV-cache pytree (see ``init_cache``) — the inference engines jit this. The
    cache is an explicit function argument, not flax mutable state, so it shards
    and donates cleanly under jit.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from jax.ad_checkpoint import checkpoint_name

from deepspeed_tpu.monitor.trace import tracer as _tracer
from deepspeed_tpu.ops.attention import dot_product_attention, reference_attention
from deepspeed_tpu.runtime import activation_checkpointing


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32        # < num_attention_heads => GQA (Mistral: 8)
    max_position_embeddings: int = 4096
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    sliding_window: Optional[int] = None  # Mistral: 4096
    qkv_bias: bool = False               # Qwen2 lineage: biased q/k/v projections
    # Gemma lineage structural flags:
    head_dim_override: Optional[int] = None  # head_dim decoupled from hidden/heads
    embed_scale_by_sqrt_dim: bool = False    # x *= sqrt(hidden) after embedding
    norm_plus_one: bool = False              # RMSNorm scales by (1 + weight)
    mlp_act: str = "silu"                    # "silu" | "gelu" (tanh) gate act
    # Ulysses sequence parallelism for training: attention runs through two
    # all-to-alls on the 'seq' mesh axis (parallel/ulysses.py); no-op when
    # the mesh has no seq axis. Requires heads and T divisible by seq size.
    sequence_parallel: bool = False
    # Ring-attention context parallelism (parallel/ring.py): KV rotates the
    # ICI ring while T stays sharded over 'seq'. The long-sequence choice
    # when head counts can't divide the seq axis. Mutually exclusive with
    # sequence_parallel.
    context_parallel: bool = False
    # rows of the batch per chunk in the fused projection+CE loss
    # (chunked_causal_lm_loss): the rows of logits alive at once. Larger
    # chunks raise head-GEMM MXU efficiency, smaller bound the transient of
    # one iteration ([chunk, T, V] fp32 logits and, under differentiation,
    # their gradient in the product's dtype); what the forward pass keeps
    # (dh, dw) does not depend on it
    lm_loss_chunk: int = 4
    dtype: Any = jnp.float32
    remat: bool = False
    remat_policy: Optional[str] = None

    def __post_init__(self):
        if ((self.sequence_parallel or self.context_parallel)
                and self.sliding_window is not None):
            raise ValueError(
                "sequence_parallel/context_parallel do not support "
                "sliding_window attention yet (both run full causal "
                "attention); unset one of the two")
        if self.sequence_parallel and self.context_parallel:
            raise ValueError("sequence_parallel and context_parallel are "
                             "mutually exclusive")

    @property
    def head_dim(self) -> int:
        if self.head_dim_override is not None:
            return self.head_dim_override
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def llama2_7b(cls, **kw):
        return cls(**kw)

    @classmethod
    def llama2_13b(cls, **kw):
        defaults = dict(hidden_size=5120, intermediate_size=13824,
                        num_hidden_layers=40, num_attention_heads=40,
                        num_key_value_heads=40)
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def llama2_70b(cls, **kw):
        defaults = dict(hidden_size=8192, intermediate_size=28672,
                        num_hidden_layers=80, num_attention_heads=64,
                        num_key_value_heads=8)
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def mistral_7b(cls, **kw):
        defaults = dict(vocab_size=32000, hidden_size=4096, intermediate_size=14336,
                        num_hidden_layers=32, num_attention_heads=32,
                        num_key_value_heads=8, max_position_embeddings=32768,
                        rope_theta=1e6, sliding_window=4096)
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def tiny(cls, **kw):
        """Fixture-sized config (analog of the reference's ``simple_model.py``
        fixtures)."""
        defaults = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                        num_hidden_layers=2, num_attention_heads=4,
                        num_key_value_heads=2, max_position_embeddings=128)
        defaults.update(kw)
        return cls(**defaults)


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.float32
    plus_one: bool = False   # Gemma: y * (1 + weight), weight zero-centred

    @nn.compact
    def __call__(self, x):
        init = nn.initializers.zeros if self.plus_one else nn.initializers.ones
        w = self.param("weight", init, (x.shape[-1],))
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + self.eps)
        scale = (1.0 + w) if self.plus_one else w
        return (y * scale).astype(self.dtype)


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding, interleaved-pair convention. x: [B, T, H, D],
    positions: [B, T] (int). Parity: the reference's apply_rotary_pos_emb kernel
    (csrc/transformer/inference/csrc/apply_rotary_pos_emb.cu) — on TPU a pure
    jnp rotation that XLA fuses into the surrounding matmuls."""
    D = x.shape[-1]
    freqs = rope_frequencies(D, theta)                     # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, T, D/2]
    cos = jnp.cos(angles)[:, :, None, :]                   # [B, T, 1, D/2]
    sin = jnp.sin(angles)[:, :, None, :]
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """[B, T, H_kv, D] -> [B, T, H_kv*n_rep, D] (GQA head expansion)."""
    if n_rep == 1:
        return x
    B, T, H, D = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (B, T, H, n_rep, D)).reshape(B, T, H * n_rep, D)


def _window_bias(q_positions: jax.Array, k_positions: jax.Array,
                 window: Optional[int]) -> jax.Array:
    """Additive bias [B, 1, Tq, Tk]: causal (key pos <= query pos), optionally
    restricted to the sliding window [q - window + 1, q]. Per-batch-row positions
    so left-padded / ragged batches mask correctly."""
    delta = q_positions[:, :, None] - k_positions[:, None, :]
    ok = delta >= 0
    if window is not None:
        ok = ok & (delta < window)
    return jnp.where(ok, 0.0, jnp.finfo(jnp.float32).min)[:, None]


def sliding_window_attention(q, k, v, positions, window: int) -> jax.Array:
    """O(T·w) local attention: queries in block i attend keys in blocks i-1 and i
    (block size = window, so [q-w+1, q] is always covered). Parity role: the
    reference's long-sequence lever is block-sparse Triton attention
    (ops/sparse_attention, 'bslongformer' pattern); this is the same banded
    structure expressed as a blocked einsum XLA tiles onto the MXU — no [T, T]
    score materialisation."""
    B, T, H, D = q.shape
    w = window
    nb = -(-T // w)
    pad = nb * w - T
    if pad:
        padw = ((0, 0), (0, pad), (0, 0), (0, 0))
        q, k, v = (jnp.pad(t, padw) for t in (q, k, v))
        # padded queries mask themselves out via positions = -inf sentinel
        positions = jnp.pad(positions, ((0, 0), (0, pad)), constant_values=-(10 ** 9))
    blk = lambda t: t.reshape(B, nb, w, H, D)
    qb, kb, vb = blk(q), blk(k), blk(v)
    def shift(t, fill=0):
        pad_cfg = ((0, 0), (1, 0)) + ((0, 0),) * (t.ndim - 2)
        return jnp.pad(t, pad_cfg, constant_values=fill)[:, :-1]

    k2 = jnp.concatenate([shift(kb), kb], axis=2)          # [B, nb, 2w, H, D]
    v2 = jnp.concatenate([shift(vb), vb], axis=2)
    pb = positions.reshape(B, nb, w)
    # phantom block before block 0 carries +inf-like positions => delta < 0 => masked
    pk2 = jnp.concatenate([shift(pb, fill=2 ** 30), pb], axis=2)  # [B, nb, 2w]
    delta = pb[..., :, None] - pk2[..., None, :]            # [B, nb, w, 2w]
    ok = (delta >= 0) & (delta < w)
    bias = jnp.where(ok, 0.0, jnp.finfo(jnp.float32).min)[:, :, None]  # [B,nb,1,w,2w]
    scale = 1.0 / (D ** 0.5)
    scores = jnp.einsum("bnqhd,bnkhd->bnhqk", qb, k2).astype(jnp.float32) * scale
    probs = jax.nn.softmax(scores + bias, axis=-1).astype(q.dtype)
    out = jnp.einsum("bnhqk,bnkhd->bnqhd", probs, v2).reshape(B, nb * w, H, D)
    return out[:, :T]


class LlamaAttention(nn.Module):
    config: LlamaConfig

    def setup(self):
        cfg = self.config
        dense = lambda feats, name, bias=False: nn.Dense(
            feats, use_bias=bias, dtype=cfg.dtype, name=name)
        qb = cfg.qkv_bias
        self.q_proj = dense(cfg.num_attention_heads * cfg.head_dim, "q_proj", qb)
        self.k_proj = dense(cfg.num_key_value_heads * cfg.head_dim, "k_proj", qb)
        self.v_proj = dense(cfg.num_key_value_heads * cfg.head_dim, "v_proj", qb)
        self.o_proj = dense(cfg.hidden_size, "o_proj")

    def _qkv(self, x, positions):
        cfg = self.config
        B, T, _ = x.shape
        q = self.q_proj(x).reshape(B, T, cfg.num_attention_heads, cfg.head_dim)
        k = self.k_proj(x).reshape(B, T, cfg.num_key_value_heads, cfg.head_dim)
        v = self.v_proj(x).reshape(B, T, cfg.num_key_value_heads, cfg.head_dim)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        return q, k, v

    def __call__(self, x, positions):
        cfg = self.config
        B, T, _ = x.shape
        q, k, v = self._qkv(x, positions)
        if cfg.sequence_parallel:
            # Ulysses (DeepSpeed sequence parallelism, sequence/layer.py:60):
            # T shards over the 'seq' mesh axis; two all-to-alls around local
            # attention. K/V stay at Hkv heads across the wire — the GQA
            # repeat happens post-scatter inside the local attention, so the
            # all-to-all moves 1/n_rep of the repeated volume. No-op when the
            # mesh's seq axis is 1. (sliding_window rejected in __post_init__)
            from deepspeed_tpu.parallel.ulysses import sequence_parallel_attention
            out = sequence_parallel_attention(q, k, v, causal=True)
        elif cfg.context_parallel:
            from deepspeed_tpu.parallel.ulysses import context_parallel_attention
            out = context_parallel_attention(q, k, v, causal=True)
        else:
            n_rep = cfg.num_attention_heads // cfg.num_key_value_heads
            k, v = repeat_kv(k, n_rep), repeat_kv(v, n_rep)
            if cfg.sliding_window is not None and T > cfg.sliding_window:
                out = sliding_window_attention(q, k, v, positions,
                                               cfg.sliding_window)
            else:
                out = dot_product_attention(q, k, v, causal=True)
        out = checkpoint_name(
            out.reshape(B, T, cfg.num_attention_heads * cfg.head_dim), "attn_out")
        return self.o_proj(out)

    def decode(self, x, positions, layer_cache, cache_index):
        """Incremental step: append this step's K/V at ``cache_index`` and attend
        over the filled prefix. layer_cache: {"k","v"}: [B, S_max, H_kv, D] —
        or the int8 tier with "k_scale"/"v_scale" [B, S_max, H_kv] f32
        (quantize on append, dequant fused into the attention read)."""
        cfg = self.config
        B, T, _ = x.shape
        q, k, v = self._qkv(x, positions)
        new_cache = {}
        if "k_scale" in layer_cache:
            # ADVICE r4: dequant FOLDED into the attention dots (see
            # quantized_cache_attention) — no dequantized [B, S_max, Hkv, D]
            # cache nor its repeat_kv is ever materialised, so the transient
            # peak that offset the tier's 1.94x capacity gain is gone.
            new_cache = quantized_cache_append(layer_cache, k, v, cache_index)
            S = new_cache["k"].shape[1]
            k_pos = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
            bias = _window_bias(positions, k_pos, cfg.sliding_window)
            out = quantized_cache_attention(q, new_cache, bias,
                                            cfg.num_key_value_heads)
            out = self.o_proj(out.reshape(
                B, T, cfg.num_attention_heads * cfg.head_dim))
            return out, new_cache
        else:
            ck = jax.lax.dynamic_update_slice(
                layer_cache["k"], k.astype(layer_cache["k"].dtype),
                (0, cache_index, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                layer_cache["v"], v.astype(layer_cache["v"].dtype),
                (0, cache_index, 0, 0))
            new_cache = {"k": ck, "v": cv}
        S = ck.shape[1]
        n_rep = cfg.num_attention_heads // cfg.num_key_value_heads
        kk, vv = repeat_kv(ck, n_rep), repeat_kv(cv, n_rep)
        # mask: key slot j visible iff its position <= this row's query position
        # (covers prefill + decode), within the sliding window when configured
        k_pos = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        bias = _window_bias(positions, k_pos, cfg.sliding_window)
        out = reference_attention(q, kk, vv, bias=bias)
        out = self.o_proj(out.reshape(B, T, cfg.num_attention_heads * cfg.head_dim))
        return out, new_cache


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def gated_activation(act, as_values, gate, up):
    """``act(gate) * up``, the MLP's gate. No gradient asked (evaluation,
    decoding): the plain expression, for the compiler to fuse where it
    likes. Under differentiation, with ``as_values``, ``act(gate) * up`` and
    its gradient ``(dgate, dup)`` are values of their own: as plain
    expressions the TPU compiler made each a producer inside the operand of
    the two products that read it — six products a layer, each re-forming
    ``act(gate)`` (an ``exp`` and a divide an element) tile by tile, at
    1.2-1.7 times the product's own time where the weight's AdamW update
    rides in the same fusion (PR 44). The rule keeps ``gate`` and ``up``,
    which a rung that keeps the dots keeps anyway, and rounds where autodiff
    rounds."""
    return act(gate) * up


def _gated_fwd(act, as_values, gate, up):
    h = act(gate) * up
    return (jax.lax.optimization_barrier(h) if as_values else h), (gate, up)


def _gated_bwd(act, as_values, saved, dh):
    gate, up = saved
    a, act_vjp = jax.vjp(act, gate)
    (dgate,) = act_vjp(dh * up)
    grads = (dgate, dh * a)
    return jax.lax.optimization_barrier(grads) if as_values else grads


gated_activation.defvjp(_gated_fwd, _gated_bwd)


class LlamaMLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        gate = nn.Dense(cfg.intermediate_size, use_bias=False, dtype=cfg.dtype,
                        name="gate_proj")(x)
        up = nn.Dense(cfg.intermediate_size, use_bias=False, dtype=cfg.dtype,
                      name="up_proj")(x)
        # not in a step that sums its weight gradients across devices (the
        # engine says so around its trace): no AdamW update rides in the
        # products there, so there is little to take, and the products that
        # would form the values as their epilogues also carry an all-gather's
        # pieces and pay 0.6 ms each for it (PR 44)
        h = gated_activation(
            nn.gelu if cfg.mlp_act == "gelu" else nn.silu,
            not activation_checkpointing.gradients_are_reduced(), gate, up)
        return nn.Dense(cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                        name="down_proj")(h)


class LlamaBlock(nn.Module):
    config: LlamaConfig

    def setup(self):
        cfg = self.config
        self.input_layernorm = RMSNorm(cfg.rms_norm_eps, cfg.dtype,
                                       cfg.norm_plus_one, name="input_layernorm")
        self.post_attention_layernorm = RMSNorm(cfg.rms_norm_eps, cfg.dtype,
                                                cfg.norm_plus_one,
                                                name="post_attention_layernorm")
        self.self_attn = LlamaAttention(cfg, name="self_attn")
        self.mlp = LlamaMLP(cfg, name="mlp")

    def __call__(self, x, positions):
        x = x + self.self_attn(self.input_layernorm(x), positions)
        return x + self.mlp(self.post_attention_layernorm(x))

    def decode(self, x, positions, layer_cache, cache_index):
        a, new_cache = self.self_attn.decode(self.input_layernorm(x), positions,
                                             layer_cache, cache_index)
        x = x + a
        return x + self.mlp(self.post_attention_layernorm(x)), new_cache


def causal_lm_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean next-token NLL with shift-by-one (shared by the CausalLM heads).

    logsumexp form: NLL = logsumexp(logits) - logits[label]. Unlike
    log_softmax + gather, this never materialises a second [B, T, V] fp32
    array — on TPU the vocab dim dominates activation memory/bandwidth
    (V=50k fp32 is ~1.6 GB at B=8, T=1024)."""
    logits_s = logits[:, :-1, :]
    labels_s = labels[:, 1:]
    lse = jax.scipy.special.logsumexp(logits_s, axis=-1)
    picked = jnp.take_along_axis(logits_s, labels_s[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def _head_chunk(h, y, w, bias, transpose):
    """One chunk of the loss head: ``h`` [chunk, T-1, C] and ``w`` ([C, V] if
    ``transpose`` else [V, C]) in the product's dtype, ``bias`` float32 or
    None. Returns the sum of the chunk's next-token NLL, its float32 logits
    and their log-sum-exp."""
    logits = jax.lax.dot_general(
        h, w, (((2,), (0 if transpose else 1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if bias is not None:
        logits = logits + bias
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked), logits, lse


def _head_chunk_grads(h, y, w, bias, transpose, inv_n):
    """:func:`_head_chunk`'s NLL with the gradient of the MEAN loss
    (``inv_n``: 1 / all rows of the loss), formed while the chunk's logits
    are alive: ``dlogits = (softmax - onehot) * inv_n`` in float32, rounded
    once to the product's dtype for the two transposed products. Returns
    ``(nll, dw, dbias)`` in float32, for the caller to sum over chunks, and
    ``dh`` in ``h``'s dtype."""
    # two products read ``h``: it stays a value of its own (as it was while
    # it was a residual), or the TPU compiler fuses the final norm into both
    # as their producer and each runs a quarter slower
    h = jax.lax.optimization_barrier(h)
    nll, logits, lse = _head_chunk(h, y, w, bias, transpose)
    hot = jax.lax.broadcasted_iota(y.dtype, logits.shape, 2) == y[..., None]
    dlogits = (jnp.exp(logits - lse[..., None]) - hot) * inv_n
    dbias = None if bias is None else jnp.sum(dlogits, axis=(0, 1))
    # ... and so does ``dlogits``, written once in the product's dtype: fused
    # into the transposed products, each reads the float32 logits and runs
    # the exponential again (the ``dw`` product 11.4 ms for 6.8, PR 36)
    dlogits = jax.lax.optimization_barrier(dlogits.astype(h.dtype))
    dh = jax.lax.dot_general(
        dlogits, w, (((2,), (1 if transpose else 0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dw = jax.lax.dot_general(*((h, dlogits) if transpose else (dlogits, h)),
                             (((0, 1), (0, 1)), ((), ())),
                             preferred_element_type=jnp.float32)
    return (nll, dw, dbias), dh.astype(h.dtype)


def _head_chunks(x, labels, chunk):
    B, T, C = x.shape
    return (x[:, :-1, :].reshape(B // chunk, chunk, T - 1, C),
            labels[:, 1:].reshape(B // chunk, chunk, T - 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _loss_head(x, w, bias, labels, chunk, transpose):
    """No gradient asked (evaluation, an abstract trace): the plain scan."""
    def body(acc, inp):
        return acc + _head_chunk(*inp, w, bias, transpose)[0], None

    total, _ = jax.lax.scan(body, jnp.float32(0.0),
                            _head_chunks(x, labels, chunk))
    return total / (x.shape[0] * (x.shape[1] - 1))


def _loss_head_fwd(x, w, bias, labels, chunk, transpose):
    B, T, _ = x.shape
    xs, ys = _head_chunks(x, labels, chunk)
    n = B * (T - 1)
    # noted when traced: the rows whose gradient is formed with their loss
    _tracer.note("train/loss_head/fused", B)

    def body(sums, inp):
        new, dh = _head_chunk_grads(*inp, w, bias, transpose, 1.0 / n)
        return jax.tree_util.tree_map(jnp.add, sums, new), dh

    zeros = (jnp.float32(0.0), jnp.zeros(w.shape, jnp.float32),
             None if bias is None else jnp.zeros(bias.shape, jnp.float32))
    (total, dw, dbias), dh = jax.lax.scan(body, zeros, (xs, ys))
    return total / n, (dh.reshape(B, T - 1, -1), dw.astype(w.dtype), dbias)


def _loss_head_bwd(chunk, transpose, saved, g):
    # the cotangent (the loss scale, a caller's 1/k) multiplies in float32
    dh, dw, dbias = jax.tree_util.tree_map(
        lambda d: (d.astype(jnp.float32) * g).astype(d.dtype), saved)
    return jnp.pad(dh, ((0, 0), (0, 1), (0, 0))), dw, dbias, None


_loss_head.defvjp(_loss_head_fwd, _loss_head_bwd)


def chunked_causal_lm_loss(x: jax.Array, vocab_weight: jax.Array,
                           labels: jax.Array, batch_chunk: int = 4,
                           transpose: bool = False,
                           head_bias: Optional[jax.Array] = None) -> jax.Array:
    """Fused projection + cross entropy over batch chunks.

    ``x`` [B, T, C] final hidden states; ``vocab_weight`` [V, C] (embedding
    layout; pass ``transpose=True`` for a [C, V] lm_head kernel). The [B, T, V]
    logits tensor never materialises: a scan walks ``batch_chunk`` rows of
    the batch at a time, and a chunk's logits (~chunk*T*V fp32 transient)
    live only inside its iteration, which is what lets large-vocab models
    run at memory-bound batch sizes — the role of the reference's fused
    logits kernels (inference/v2 logits_gather + vocab-parallel loss in
    Megatron-style training).

    Under differentiation (a ``jax.custom_vjp``) the same iteration that
    forms a chunk's logits also forms the loss's gradient with respect to
    them, ``(softmax - onehot) / N``, and pushes it through the two
    transposed products. What the forward pass keeps for the backward pass
    is then ``dh`` ([B, T-1, C] in the product's dtype) and ``dw`` (the
    head's gradient, float32 across chunks, kept in the weight's dtype;
    ``dbias`` beside it) — neither logits nor a second run of the vocabulary
    product — and the backward rule only multiplies them by the loss's
    scalar cotangent (the loss scale). The loss is the last thing the
    forward pass computes and the first the backward pass differentiates, so
    nothing is held longer than autodiff would hold it. Its operations carry
    the scope ``loss_head`` in a device trace, and ``train/loss_head/fused``
    in ``tracer.totals`` (rows of the batch, noted when a gradient is
    traced) says the fused rule engaged.
    """
    B = x.shape[0]
    chunk = max(1, min(batch_chunk, B))
    while B % chunk:
        chunk -= 1
    # bf16 models project in bf16 with fp32 MXU accumulation (the v5e runs
    # fp32 matmuls at a fraction of bf16 rate; accumulation stays exact).
    # fp32 models keep the fp32 path bit-for-bit; fp16 models take it too,
    # so the loss scale multiplies float32 gradients before the cast back.
    mm_dtype = jnp.bfloat16 if x.dtype == jnp.bfloat16 else jnp.float32
    with jax.named_scope("loss_head"):
        return _loss_head(
            x.astype(mm_dtype), vocab_weight.astype(mm_dtype),
            None if head_bias is None else head_bias.astype(jnp.float32),
            labels, chunk, transpose)


def decode_layers(model, input_ids, cache, cache_index, positions):
    """Shared incremental-decode trunk for the CausalLM heads (duck-typed over
    ``embed_tokens``/``layers``/``norm``/``lm_head``). Returns (logits, cache)."""
    B, T = input_ids.shape
    if positions is None:
        positions = cache_index + jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    x = model.embed_tokens(input_ids)
    if getattr(model.config, "embed_scale_by_sqrt_dim", False):
        x = (x.astype(jnp.float32)
             * (model.config.hidden_size ** 0.5)).astype(x.dtype)
    new_cols = {key: [] for key in cache}
    for i, layer in enumerate(model.layers):
        layer_cache = {key: cache[key][i] for key in cache}
        x, nc = layer.decode(x, positions, layer_cache, cache_index)
        for key in new_cols:
            new_cols[key].append(nc[key])
    x = model.norm(x)
    logits = model.lm_head(x).astype(jnp.float32)
    return logits, {key: jnp.stack(cols) for key, cols in new_cols.items()}


class LlamaForCausalLM(nn.Module):
    """Training: ``__call__(batch)`` -> loss (engine contract). Inference:
    ``apply(..., method='forward_logits'/'decode')``."""

    config: LlamaConfig

    def setup(self):
        cfg = self.config
        self.embed_tokens = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                                     dtype=cfg.dtype, name="embed_tokens")
        self.layers = [LlamaBlock(cfg, name=f"layers_{i}")
                       for i in range(cfg.num_hidden_layers)]
        self.norm = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.norm_plus_one,
                            name="norm")
        self.lm_head = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                                name="lm_head")

    def _trunk(self, input_ids, positions):
        cfg = self.config
        x = self.embed_tokens(input_ids)
        if cfg.embed_scale_by_sqrt_dim:
            # Gemma normaliser; fp32 round-trip matches HF's bf16 cast order
            x = (x.astype(jnp.float32) * (cfg.hidden_size ** 0.5)).astype(x.dtype)
        x = activation_checkpointing.apply_checkpointed_layers(
            self, x, lambda mdl, h, i: mdl.layers[i](h, positions),
            cfg.num_hidden_layers, cfg.remat, cfg.remat_policy,
            layers=self.layers, layer_args=(positions,))
        return self.norm(x)

    def forward_logits(self, input_ids, positions=None):
        B, T = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        x = self._trunk(input_ids, positions)
        return self.lm_head(x).astype(jnp.float32)

    def __call__(self, batch, deterministic: bool = True):
        if isinstance(batch, dict):
            input_ids = batch["input_ids"]
            labels = batch.get("labels", input_ids)
        else:
            input_ids, labels = batch, batch
        B, T = input_ids.shape
        positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        x = self._trunk(input_ids, positions)
        # instantiate the head params (negligible [B,1,V] call, DCE'd after
        # init), then fused chunked projection+CE — the [B,T,V] logits never
        # materialise (chunked_causal_lm_loss)
        _ = self.lm_head(x[:, :1])
        kernel = self.lm_head.variables["params"]["kernel"]
        return chunked_causal_lm_loss(x, kernel, labels, transpose=True,
                                      batch_chunk=self.config.lm_loss_chunk)

    def decode(self, input_ids, cache, cache_index, positions=None):
        """One incremental step (prefill or single-token decode).

        input_ids: [B, T]; cache: pytree from ``init_cache`` — {"k","v"}:
        [L, B, S_max, H_kv, D]; cache_index: int32 write offset.
        Returns (logits [B, T, V] fp32, new_cache)."""
        return decode_layers(self, input_ids, cache, cache_index, positions)


def quantized_cache_append(layer_cache, k, v, cache_index):
    """Quantize this step's K/V rows (per token-head symmetric int8) and
    append them to an int8 dense cache (v1 KV tier; ZeRO-Inference analog,
    reference README.md:23). Returns the updated cache dict."""
    new_cache = {}
    for name, rows in (("k", k), ("v", v)):
        scale = jnp.max(jnp.abs(rows.astype(jnp.float32)),
                        axis=-1) / 127.0                        # [B,T,Hkv]
        scale = jnp.maximum(scale, 1e-8)
        q8 = jnp.clip(jnp.round(rows.astype(jnp.float32) / scale[..., None]),
                      -127, 127).astype(jnp.int8)
        new_cache[name] = jax.lax.dynamic_update_slice(
            layer_cache[name], q8, (0, cache_index, 0, 0))
        new_cache[f"{name}_scale"] = jax.lax.dynamic_update_slice(
            layer_cache[f"{name}_scale"], scale, (0, cache_index, 0))
    return new_cache


def quantized_cache_attention(q, cache, bias, num_kv_heads,
                              softmax_scale=None):
    """Attention over an int8 dense cache with the dequant FOLDED into the
    dots (ADVICE r4): per-token-head scales multiply score columns (K) and
    p (V) — no dequantized [B, S, Hkv, D] cache and no repeat_kv to H heads
    is ever materialised.

    q [B, T, H, D]; cache {"k","v" int8 [B,S,Hkv,D], "k_scale","v_scale"
    [B,S,Hkv] f32}; bias additive f32 [B, 1|H, T, S] (window mask and/or
    ALiBi). Returns [B, T, H, D] in q's dtype."""
    B, T, H, D = q.shape
    S = cache["k"].shape[1]
    Hkv = num_kv_heads
    G = H // Hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / (D ** 0.5)
    qg = q.reshape(B, T, Hkv, G, D).astype(jnp.float32)
    sc = jnp.einsum("btkgd,bskd->btkgs", qg,
                    cache["k"].astype(jnp.float32)) * scale
    sc = sc * cache["k_scale"].astype(jnp.float32) \
        .transpose(0, 2, 1)[:, None, :, None, :]
    bias_b = jnp.broadcast_to(bias, (B, H, T, S)) \
        .reshape(B, Hkv, G, T, S).transpose(0, 3, 1, 2, 4)
    p = jax.nn.softmax(sc + bias_b, axis=-1)
    pv = p * cache["v_scale"].astype(jnp.float32) \
        .transpose(0, 2, 1)[:, None, :, None, :]
    out = jnp.einsum("btkgs,bskd->btkgd", pv,
                     cache["v"].astype(jnp.float32))
    return out.reshape(B, T, H, D).astype(q.dtype)


def init_cache(config: LlamaConfig, batch_size: int, max_len: int,
               dtype: Any = None, kv_bits: Any = None) -> Dict[str, jax.Array]:
    """Dense per-sequence KV cache (inference v1 path; the v2 engine uses the
    blocked/paged cache in deepspeed_tpu.inference.ragged instead).

    ``kv_bits=8``: int8 storage with per-token-per-head f32 scales
    (ZeRO-Inference KV tier — the persistent cache halves, so servable
    context x batch at fixed HBM ~doubles; reference README.md:23)."""
    dtype = dtype or config.dtype
    shape = (config.num_hidden_layers, batch_size, max_len,
             config.num_key_value_heads, config.head_dim)
    if kv_bits == 8:
        sshape = shape[:-1]
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(sshape, jnp.float32),
                "v_scale": jnp.zeros(sshape, jnp.float32)}
    if kv_bits is not None:
        raise ValueError(f"kv_bits must be None or 8, got {kv_bits!r}")
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
