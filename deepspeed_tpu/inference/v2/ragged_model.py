"""Ragged model implementations for the v2 engine: the traced programs.

Parity: reference ``inference/v2/model_implementations/`` (llama_v2, mistral,
mixtral, opt, falcon, phi — each a hand-assembled stack of DSModule kernels over a
ragged batch) and the module registry in ``inference/v2/modules``. TPU-native
re-design: ONE generic ragged forward — a ``lax.scan`` over layer-stacked weights —
specialised per family by a :class:`RaggedModelSpec` (norm type, activation,
rope/learned positions, parallel residual, MoE; ``model_spec.py``) and a weight
*adapter* that re-keys the zoo model's param tree into the canonical stacked
layout (``adapters/``, one module a family). This module holds what is traced:
the layer body, the MoE FFN, the mixers, the page writes and the builders.

Pass structure (see ``ragged/ragged_batch.py``): tokens = [NC prompt-chunk
slots | decode rows]. Each layer writes the pass's K/V into the paged cache
(a chunk slot's rows as one run, ``_kv_run_write``; the decode rows by a flat
scatter, ``_kv_page_write``), then attends:

  - chunk slots -> ``AttentionKernelSpec.chunk`` (flash over pages for all
    slots in one kernel, causal by absolute position)
  - decode rows -> ``AttentionKernelSpec.decode`` (one token per sequence;
    the fused decode step uses ``.decode_step``/``.sidebuf``)

Every builder routes attention through ONE ``AttentionKernelSpec``
(``inference/v2/attention.py``): kernel variants key on the pool dtype at
the call (``kv_scales=None`` = bf16/f32 pages), window/alibi/TP bind once.

MoE layers use sort-based grouped GEMM (``ops/pallas/grouped_matmul.py`` for
bfloat16 stacks of small expert matrices, ``jax.lax.ragged_dot`` otherwise:
``moe_grouped_kernel``) — the TPU
analog of the reference's CUTLASS ``moe_gemm`` + moe_scatter/gather
(``inference/v2/kernels/cutlass_ops``, ``ragged_ops/moe_{scatter,gather}``).
The expert stacks ``[L, E, K, N]`` are NOT scanned with the other layer
weights: the grouped GEMM is a custom call, a scan's per-layer slice cannot
fuse into it, and the compiler copied each layer's stacks to a temporary
first. The layer body closes over the whole stacks and ``_moe_ffn`` addresses
layer ``l`` as groups ``[l*E, (l+1)*E)`` of the ``[L*E, K, N]`` view, no
other group read (``_split_expert_stacks``; docs/SERVING.md "MoE layers").
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import functools
import math

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.attention import (BLOCK_DIFFUSION_MSG,
                                                  STATE_SNAPSHOT_MSG,
                                                  AttentionKernelSpec)
from deepspeed_tpu.inference.v2.model_spec import (
    RaggedModelSpec, _pool_index, index_width, latent_width, layer_units,
    num_page_layers, num_state_layers)
from deepspeed_tpu.inference.v2.ragged.state_pool import StatefulKV
from deepspeed_tpu.monitor.trace import tracer as _tracer
from deepspeed_tpu.ops.pallas.gdn import gdn_chunk_scan, gdn_decode_step
from deepspeed_tpu.ops.pallas.grouped_matmul import (grouped_matmul,
                                                     plan_visits, row_tile)
from deepspeed_tpu.ops.pallas.paged_attention import (
    KvRunPlan, _scale_tile_rows, kv_quantize_rows, kv_run_group, kv_run_plan,
    kv_write_dequant, paged_kv_row_write, paged_kv_run_write)
from deepspeed_tpu.ops.pallas.power_retention import (pr_chunk_scan,
                                                      pr_decode_step)
from deepspeed_tpu.ops.pallas.ssm import (ssd_chunk_scan, ssd_decode_step,
                                          ssm_chunk_scan, ssm_decode_step)

# The names the benchmark's accepted files (chipbench/, tests/chipbench/)
# still read through this module, though they live in model_spec.py and
# adapters/ now. Nothing else imports them from here, and the block goes
# when a `benchmark` PR repoints those files (ROADMAP D22).
from deepspeed_tpu.inference.v2.adapters import (  # noqa: F401
    ADAPTERS, adapt_glm_dsa, adapt_zaya)
from deepspeed_tpu.inference.v2.adapters.zaya import (  # noqa: F401
    zaya_channel_order)
from deepspeed_tpu.inference.v2.model_spec import (  # noqa: F401
    describe_layer_kinds, layer_runs)


def _kv_unpack(kp):
    """KV pool argument -> (pages, scales-or-None). The combined pool
    [L, NB, 2, Hkv, bs, D] holds K (index 0) and V (index 1) in ONE page —
    the decode kernel is per-DMA-copy bound, so one value copy per page
    (see ops/pallas/paged_attention.py module docstring). int8 pools travel
    as a (values int8, per-token-head f32 scale TILES [L, NB, R8, 128]) tuple
    through every jit boundary so the plumbing is dtype-agnostic."""
    if isinstance(kp, tuple):
        return kp
    return kp, None


def _state_unpack(kp):
    """A program's pool argument -> (the KV pool as :func:`_kv_unpack`
    takes it, the recurrent-state pools ``(ssm, conv)`` or None). A model
    with state-space layers is handed a :class:`StatefulKV`; any other the
    bare pool, and its programs carry no state argument at all."""
    if isinstance(kp, StatefulKV):
        return kp.pages, (kp.ssm, kp.conv)
    return kp, None


def _state_pack(new_kv, state):
    return new_kv if state is None else StatefulKV(new_kv, *state)


# --------------------------------------------------------------------------- #
# generic ragged forward
# --------------------------------------------------------------------------- #

def _norm(x, w, kind: str, eps: float, dtype, plus_one: bool = False):
    xf = x.astype(jnp.float32)
    scale = (1.0 + w["scale"]) if plus_one else w["scale"]
    if kind == "rms":
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * scale
    else:
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + eps) * scale + w["bias"]
    return y.astype(dtype)


_PLAIN_ACTS = {
    "gelu": jax.nn.gelu,                                      # tanh approx
    "gelu_exact": lambda x: jax.nn.gelu(x, approximate=False),  # erf-exact
    "silu": jax.nn.silu,
    "relu": jax.nn.relu,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),            # nemotron_h
}


def _plain_act(name: str) -> Callable:
    """Non-gated MLP activation. Raising on unknown names (rather than a relu
    fallback) is what keeps a new zoo activation from silently serving garbage
    through the v2 path."""
    try:
        return _PLAIN_ACTS[name]
    except KeyError:
        raise ValueError(
            f"unknown MLP activation '{name}' for the ragged path "
            f"(gated: swiglu/geglu; plain: {sorted(_PLAIN_ACTS)})") from None


def _rope_flat(x: jax.Array, positions: jax.Array, theta: float,
               rotary_dim: Optional[int]) -> jax.Array:
    """Rotary embedding on [T, H, D] with per-token positions [T] — delegates to
    the zoo's single implementation (models/decoder._partial_rope) via a unit
    batch dim so v1 dense and v2 ragged paths share the exact rotation math."""
    from deepspeed_tpu.models.decoder import _partial_rope
    return _partial_rope(x[None], positions[None], theta, rotary_dim)[0]


def _split_expert_stacks(layers: Dict) -> Tuple[Dict, Dict]:
    """``weights["layers"]`` -> (the tree a layer scan slices, the expert
    stacks ``[L, E, K, N]`` the layer body closes over whole).

    A scan hands its body one layer's slice of every stacked leaf. XLA fuses
    that slice into a dense dot, but the grouped GEMM is a custom call that
    takes no fused operand: sliced, each layer's three expert stacks were
    first COPIED to a temporary (57% of the Mixtral decode step's device
    time). So the bf16 stacks stay out of the scanned tree and ``_moe_ffn``
    addresses layer ``l``'s experts inside them. int8 stacks (``{"w8",
    "scale"}``) stay scanned: their slice fuses with the dequantizing
    convert the path needs anyway. The weight tree itself is untouched.
    """
    moe = layers.get("moe")
    if not isinstance(moe, dict):
        return layers, {}
    stacks = {k: moe[k] for k in _QUANT_MLP_KEYS
              if k in moe and not isinstance(moe[k], dict)}
    rest = {k: v for k, v in moe.items() if k not in stacks}
    return {**layers, "moe": rest}, stacks


def _swiglu(x, m):
    return _mm(jax.nn.silu(_mm(x, m["w_gate"])) * _mm(x, m["w_up"]),
               m["w_down"])


def _ffn_body(rs: "RaggedModelSpec", experts=None, l0=0):
    """The scan body of layers that are an FFN alone (``rs.block ==
    "ffn"``), for every serving program: the carry's first value is ``x``,
    the rest (pools, side buffers, states) passes through untouched; ``l -
    l0`` is the layer's place in the run's expert stacks."""
    def layer_fn(carry, scanned):
        x, *rest = carry
        w, l = scanned[:2]
        x, _ = _transformer_layer(rs, w, x, None, None, experts=experts,
                                  l=l - l0)
        return (x, *rest), None

    return layer_fn


def _scan_layers(spec: "RaggedModelSpec", layers, make_body, carry,
                 extra_xs: Tuple = ()):
    """The layer loop of every serving program: one ``lax.scan`` per unit of
    :func:`layer_units`. A unit of one kind is a run of layers of that kind,
    scanned over the run's stacked weights. ``make_body(run_spec, experts,
    l0)`` returns the scan body for a run — built with the run's own spec,
    so window, rotation and FFN are static arguments of its kernels — and the
    body is handed ``(weights of the layer, its index l[, extra_xs rows of
    it])``: KV pages are addressed by ``l``, the run's expert stacks by ``l -
    l0``. ``l`` is the layer's index in the model, except in a model some of
    whose layers hold no pages (:func:`_pool_index`): there an attention
    layer's ``l`` is its rank among the attention layers (the page pool has
    that many layers), a Mamba layer's its rank among the Mamba layers (the
    state pools'), and a layer that is an FFN alone (:func:`_ffn_body`; no
    ``make_body`` is asked for it) addresses no pool. A model of one kind is
    one scan over all its layers, as it always was.

    A unit of p > 1 kinds that repeats r times holds a tuple of p stacked
    trees of r layers each; its body runs the p layers in turn, each under
    its own kind's body, layer k of repeat i at pool index ``base_k + i *
    (the unit's layers of k's sort)`` and at place ``i`` of its own expert
    stacks."""
    stacks = layers if isinstance(layers, tuple) else (layers,)
    units = layer_units(spec)
    assert len(stacks) == len(units), (len(stacks), len(units))
    index = _pool_index(spec)
    if spec.cca is not None and index != _pool_index(spec, "state"):
        # a layer that keeps a tail beside its pages is handed ONE index for
        # both pools: its rank among the layers that hold pages has to be its
        # rank among those that hold a state slot
        raise NotImplementedError(
            "layers that keep a convolution tail beside their pages, in a "
            "model where other layers address one pool only: the layer loop "
            "hands a layer one index, and its place differs between the "
            "pools")

    def body_of(rs, experts, l0):
        return (_ffn_body if rs.block == "ffn" else make_body)(rs, experts,
                                                               l0)

    for (specs, l0, n), stack in zip(units, stacks):
        p = len(specs)
        if p == 1:
            base = index[l0]
            scanned, experts = _split_expert_stacks(stack)
            xs = (scanned, jnp.arange(base, base + n, dtype=jnp.int32)) \
                + tuple(x[l0:l0 + n] for x in extra_xs)
            carry, _ = jax.lax.scan(body_of(specs[0], experts, base), carry,
                                    xs)
            continue
        assert isinstance(stack, tuple) and len(stack) == p, (l0, p)
        split = [_split_expert_stacks(s) for s in stack]
        bases = [index[l0 + k] for k in range(p)]
        strides = [index[l0 + p + k] - index[l0 + k] for k in range(p)]

        def unit_fn(carry, xs):     # traced by the scan below, in this turn
            ws, i, *extra = xs
            for k, rs in enumerate(specs):
                # the body is built here, inside the scan's trace: where its
                # layers lie in their expert stacks (l - l0 = i) depends on i
                l = bases[k] + i * strides[k]
                carry, _ = body_of(rs, split[k][1], l - i)(
                    carry, (ws[k], l) + tuple(x[k] for x in extra))
            return carry, None

        xs = (tuple(sc for sc, _ in split), jnp.arange(n, dtype=jnp.int32)) \
            + tuple(x[l0:l0 + p * n].reshape((n, p) + x.shape[1:])
                    for x in extra_xs)
        carry, _ = jax.lax.scan(unit_fn, carry, xs)
    return carry


def _kind_splits(spec: "RaggedModelSpec", run_spec: "RaggedModelSpec",
                 n_splits: int) -> int:
    """The split-K rung a run's attention takes. In a model of mixed kinds
    the rung is chosen for the full layers (their context is what grows);
    a windowed run beside them reads at most its window and stays on the
    chunk-serial kernels."""
    if spec.layer_kinds is not None and run_spec.window is not None:
        return 1
    return n_splits


#: the shape rule of :func:`moe_grouped_kernel`, fixed from the chip table in
#: PERF.md (PR 34; 8 MiB then, the largest matrix measured under Mixtral's.
#: PR 42's rows put a 9.8 MiB matrix on the Pallas kernel at 720 GB/s against
#: 82 on XLA's; ``scripts/moe_grouped_table.py`` measures it again)
GROUPED_PALLAS_MATRIX_BYTES = 12 << 20


def moe_grouped_kernel(stack, dtype) -> str:
    """Which kernel an MoE layer's grouped products take, from what is static
    at trace time: ``"pallas"`` (``ops/pallas/grouped_matmul.py``) for
    bfloat16 stacks of small matrices — many small experts, read at 700 GB/s
    where XLA's kernel reads them at 320-430 (and a 10 MiB one at 82);
    ``"xla"`` (``jax.lax.ragged_dot``) for everything else: int8 stacks
    (``{"w8", "scale"}``), float32, matrices past
    ``GROUPED_PALLAS_MATRIX_BYTES`` (Mixtral's 112 MiB: XLA's kernel is at
    80% of the HBM rate there), widths that are not whole 128-lane tiles (a
    family whose published width is not pads its stacks with zeros when it
    adapts them: :func:`_pad_expert_width`). The row count is no part of it:
    at a decode step's 256 assignments and at a prefill pass's 8,192 the
    table reads alike. ``stack`` is one of the layer's expert stacks
    ``[.., K, N]``, ``dtype`` the activations'."""
    if isinstance(stack, dict):
        return "xla"
    K, N = stack.shape[-2:]
    if not (stack.dtype == dtype == jnp.bfloat16) or K % 128 or N % 128:
        return "xla"
    if K * N * 2 > GROUPED_PALLAS_MATRIX_BYTES:
        return "xla"
    return "pallas"


def moe_route(x: jax.Array, w: Dict, top_k: int,
              routing: Optional[Dict[str, Any]] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """The router of :func:`_moe_ffn` by itself: each token's ``top_k``
    expert ids ``[T, K]`` and their weights ``[T, K]`` in float32, from
    ``x`` ``[T, hid]``, ``w["router"]`` and, if there, ``w["expert_bias"]``."""
    routing = routing or {}
    logits = x.astype(jnp.float32) @ w["router"].astype(jnp.float32)  # [T, E]
    if routing.get("score_func") == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        biased = scores
        if "expert_bias" in w:
            biased = scores + w["expert_bias"].astype(jnp.float32)
        ids = jax.lax.top_k(biased, top_k)[1]                      # [T, K]
        gates = jnp.take_along_axis(scores, ids, axis=-1)
        if routing.get("route_norm"):
            gates = gates / (gates.sum(axis=-1, keepdims=True) + 1e-20)
        gates = gates * routing.get("route_scale", 1.0)
    else:
        gates, ids = jax.lax.top_k(logits, top_k)                  # [T, K]
        gates = jax.nn.softmax(gates, axis=-1)
    return gates, ids


def moe_route_mlp(x: jax.Array, w: Dict, routing: Dict[str, Any],
                  r_in: jax.Array, eps: float
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The router that is an MLP with a state (zaya; ``routing["router"] ==
    "mlp"``): each token's choice ``[T, 1]`` among the experts and, with
    ``routing["skip"]``, one choice more (id ``num_experts``: no expert),
    its weight ``[T, 1]`` and the router's state ``[T, R]`` for the next
    layer, all float32, the products at the highest precision (the choice is
    an argmax over probabilities that lie a few hundredths apart).

    ``r = x Wd + bd + gamma * r_in`` (``router_gamma`` is zero in the first
    layer); ``logits = W3 gelu(W2 gelu(W1 nr(r) + b1) + b2)``, ``nr`` an
    RMSNorm, gelu exact; ``p = softmax(logits)``; the choice is ``argmax(p +
    router_bias)`` — the stored bias balances load, it chooses and does not
    weigh — and its weight ``p`` of it, not renormalised."""
    f32 = jnp.float32
    g = lambda name: w[name].astype(f32)
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)
    gelu = _PLAIN_ACTS["gelu_exact"]
    with jax.named_scope("mlp"):
        r = dot(x.astype(f32), g("router_down")) + g("router_down_b") \
            + g("router_gamma") * r_in
        h = r * jax.lax.rsqrt(jnp.mean(r * r, axis=-1, keepdims=True) + eps) \
            * g("router_norm")
        h = gelu(dot(h, g("router_fc1")) + g("router_fc1_b"))
        h = gelu(dot(h, g("router_fc2")) + g("router_fc2_b"))
        p = jax.nn.softmax(dot(h, g("router_out")), axis=-1)
        if not routing.get("skip"):
            p = p[:, :routing["num_experts"]]
        ids = jnp.argmax(p + g("router_bias")[:p.shape[-1]], axis=-1)[:, None]
        gates = jnp.take_along_axis(p, ids, axis=-1)
    return gates, ids.astype(jnp.int32), r


def held_rows_bound(choices: int, held: int, routed: int,
                    kernel: str) -> Optional[int]:
    """How many sorted rows one turn of a held share's compact path takes
    (:func:`_moe_ffn`), from what is static at trace time: TWICE the count a
    router that spreads its ``choices`` (rows x top-k) evenly over its
    ``routed`` outputs sends to the ``held`` experts, in whole row tiles of
    the grouped kernel (``kernel``: :func:`moe_grouped_kernel`). None where
    that is not under ``choices`` — a half held (granite, nemotron_h), every
    expert beside a skip id (zaya): the path that sorts and combines every
    choice is the cheaper there, and the program is the one it always was.
    A pass that sends more than the bound to the held experts takes a second
    turn (and a third ..), never a second program: nothing is dropped."""
    want = -(-2 * choices * held // routed)
    tm = row_tile(want) if kernel == "pallas" else 8
    bound = -(-want // tm) * tm
    return bound if bound < choices else None


def pass_held_rows_bound(spec: "RaggedModelSpec", weights: Dict,
                         rows: int) -> Optional[int]:
    """:func:`held_rows_bound` of a pass of ``rows`` rows of this model
    (``serve/moe/held_rows_bound``), None where no share is held or the
    bound is not under the pass's choices."""
    moe = spec.moe
    if moe is None or "held" not in moe:
        return None
    for layers in _layer_stacks(weights["layers"]):
        m = layers.get("moe") if isinstance(layers, dict) else None
        if isinstance(m, dict) and "w_up" in m:
            routed = (m["router"].shape[-1] if "router" in m else
                      moe["num_experts"] + bool(moe.get("skip")))
            return held_rows_bound(
                rows * moe["top_k"], moe["held"][1], routed,
                moe_grouped_kernel(m["w_up"], spec.dtype))
    return None


@jax.named_scope("moe_ffn")
def _moe_ffn(x: jax.Array, w: Dict, top_k: int, dtype, l=0,
             routing: Optional[Dict[str, Any]] = None,
             routed: Optional[Tuple[jax.Array, jax.Array]] = None,
             turns: Optional[jax.Array] = None,
             live: Optional[jax.Array] = None):
    """Sort-based token dispatch + grouped GEMM (parity: reference moe_scatter ->
    CUTLASS moe_gemm -> moe_gather, inference/v2/kernels). x: [T, hid].

    An expert matrix in ``w`` is one layer's ``[E, K, N]`` or the whole
    ``[L, E, K, N]`` stack with ``l`` the layer to use (see
    ``_split_expert_stacks``): the stack is viewed as ``L*E`` groups and the
    kernel (:func:`moe_grouped_kernel` says which of two) reads the layer's
    experts where they lie — group ``g`` is matrix ``l*E + g`` — and no
    other.

    ``routing`` is ``spec.moe``: without a ``score_func`` the router is
    Mixtral's (top-k of the logits, softmax over the chosen); ``"sigmoid"``
    scores every expert by itself, chooses with ``w["expert_bias"]`` added
    and weighs without it (the bias balances load, it is not a weight), over
    the chosen scores' sum if ``route_norm``, times ``route_scale``. A
    ``w["shared"]`` expert sees every token, unweighted — or, with a
    ``w["shared_gate"]`` ``[hid, 1]``, times ``sigmoid(x . shared_gate)``
    (qwen3_next; ``routing["shared_gate"]`` says so). Experts with a
    ``w_gate`` stack are SwiGLUs; without one they are two matrices with the
    plain activation ``routing["act"]`` between them (``"relu2"``:
    nemotron_h; absent: tanh-gelu), and so is a shared expert without one.

    ``routed`` is ``(gates, ids)`` of a router that ran before
    (:func:`moe_route_mlp`, whose state is the caller's to carry). With
    ``routing["skip"]`` an id of ``num_experts`` is the choice of no expert:
    it is dropped the way an assignment to an expert not held is — a row
    past the groups, which no grouped product visits — and the token's
    output is zero.

    ``turns`` (an int32 scalar, a program's count so far; the prefill passes
    and the decode step hand one, :func:`_router_stream`) lets a held share
    take the compact path where :func:`held_rows_bound` gives a bound ``B``:
    everything after the sort — the gather of ``x``, the plan, the grouped
    products, the activation, the combine — works on slabs of ``B`` sorted
    rows, as many as the held choices fill (one, unless the program's rows
    send more than twice the even share to the held experts), and no array
    has ``T * top_k`` rows of ``hid``. The result is then ``(out, turns +
    the turns past the first)``; without ``turns`` it is ``out``. On that
    path the rows that ``live`` ``[T]`` says hold no token (a pass's padding,
    all alike, so routed alike: a thousand such rows on one held expert would
    take a second turn for nobody) ask no expert, and their routed output is
    zero.
    """
    T, hid = x.shape
    held = (routing or {}).get("held")
    plain = _plain_act((routing or {}).get("act", "gelu"))
    if routed is None:
        E = routed_width = w["router"].shape[-1]
        with jax.named_scope("router"):
            gates, ids = moe_route(x, w, top_k, routing)
    else:
        E = routing["num_experts"]
        routed_width = E + bool(routing.get("skip"))
        gates, ids = routed
        if routing.get("skip") and held is None:
            held = (0, E)

    kernel = moe_grouped_kernel(w["w_up"], x.dtype)
    bound = None
    if turns is not None and held is not None:
        bound = held_rows_bound(T * top_k, held[1], routed_width, kernel)

    with jax.named_scope("sort"):
        if bound is None:
            tok_idx = jnp.repeat(jnp.arange(T), top_k)                 # [T*K]
        expert_ids = ids.reshape(-1)
        if held is not None:
            # this chip's share: the router chose among all E and weighed
            # over all top_k chosen; only the assignments that land on the
            # held experts are computed here. Those sort to the front, by
            # held expert; the others follow them, belong to no group (the
            # grouped GEMM visits no row past its groups) and weigh nothing
            first, E = held
            local = expert_ids - first
            on = (local >= 0) & (local < E)
            if bound is not None and live is not None:
                on = on & jnp.repeat(live, top_k)
            expert_ids = jnp.where(on, local, E)
            gates = jnp.where(on.reshape(gates.shape), gates, 0.0)
        _tracer.bump(f"serve/moe/grouped_kernel/{kernel}")
        if bound is None:
            order = jnp.argsort(expert_ids)
            # the Pallas kernel walks whole row tiles. XLA:TPU runs its own
            # grouped-GEMM kernel only on a row count that is a multiple of
            # 8; any other count lowers to a dense product over EVERY group
            # of the rhs, all other layers' experts included. Rows past the
            # last group belong to no expert and are dropped from ys.
            tm = row_tile(order.shape[0]) if kernel == "pallas" else 8
            rows = jnp.pad(order, (0, -order.shape[0] % tm))
            xs = x[tok_idx[rows]]                              # [T*K + pad, hid]
            group_sizes = jnp.bincount(expert_ids, length=E).astype(jnp.int32)
            visits = None
            if kernel == "pallas":  # one plan for the layer's three products
                visits = plan_visits(group_sizes, rows.shape[0], tm)
            row_e = expert_ids[rows]
            if held is not None:
                row_e = jnp.minimum(row_e, E - 1)

    def experts_of(xs, sizes, visits, row_e):
        """The experts' output for sorted rows ``xs`` ``[M, hid]``, group
        ``g`` holding ``sizes[g]`` of them (``visits``: the Pallas kernel's
        plan of them; ``row_e``: each row's group, for an int8 stack's
        scales); rows of no group hold nothing defined."""

        def gg(lhs, rhs):
            if isinstance(rhs, dict) and "w8" in rhs:
                # int8 expert stacks (ADVICE r4: the experts are the dominant
                # streamed bytes of an MoE serving step — leaving them bf16
                # made quantization.weight_bits a silent no-op on mixtral).
                # The per-(expert, output-column) scale applies per ROW of
                # the grouped output, indexed by the row's expert.
                raw = jax.lax.ragged_dot(lhs, rhs["w8"].astype(lhs.dtype),
                                         sizes,
                                         preferred_element_type=jnp.float32)
                return (raw * rhs["scale"][row_e, 0, :]).astype(lhs.dtype)
            groups = rhs.reshape((-1,) + rhs.shape[-2:])       # [L*E, K, N]
            if kernel == "pallas":
                # each touched expert read once where it lies: group g of
                # this layer is matrix l*E + g, and an empty group is never
                # fetched
                return grouped_matmul(lhs, groups, visits,
                                      l if groups.shape[0] != E else 0)
            # XLA's kernel takes its sizes over every group of the rhs: the
            # layer's at offset l*E among zeros
            full = sizes
            if groups.shape[0] != E:
                full = jax.lax.dynamic_update_slice(
                    jnp.zeros(groups.shape[0], jnp.int32), sizes, (l * E,))
            return jax.lax.ragged_dot(lhs, groups.astype(lhs.dtype), full)

        if "w_gate" in w:
            h = jax.nn.silu(gg(xs, w["w_gate"])) * gg(xs, w["w_up"])
        else:       # two matrices an expert: ``routing["act"]`` between them
            h = plain(gg(xs, w["w_up"]))
        return gg(h, w["w_down"])

    if bound is None:
        with jax.named_scope("experts"):
            ys = experts_of(xs, group_sizes, visits, row_e)[:order.shape[0]]
            if held is not None:    # rows of no group hold nothing defined
                ys = jnp.where((expert_ids[order] < E)[:, None], ys, 0)
        with jax.named_scope("combine"):
            scale = gates.reshape(-1)[order].astype(ys.dtype)
            # scatter-free combine: invert the sort permutation and sum the
            # K choices (parallel/moe.py dropless_moe — TPU scatter-add
            # serializes)
            inv = jnp.argsort(order)
            out = (ys * scale[:, None])[inv].reshape(T, top_k, hid).sum(axis=1)
    else:
        out, over = _held_compact(x, expert_ids, gates.reshape(-1), top_k,
                                  E, bound, kernel, experts_of)
        turns = turns + over
    if "shared" in w:
        with jax.named_scope("shared"):
            sh = w["shared"]
            shared = (_swiglu(x, sh) if "w_gate" in sh else
                      _mm(plain(_mm(x, sh["w_up"])), sh["w_down"]))
            if "shared_gate" in w:      # one dot product a token (qwen3_next)
                shared = shared * jax.nn.sigmoid(
                    _mm(x, w["shared_gate"]).astype(jnp.float32))
            out = out + shared
    out = out.astype(dtype)
    return out if turns is None else (out, turns)


def _combine_chunk(bound: int, tm: int) -> int:
    """Rows a step of the compact path's combine (:func:`_held_compact`):
    the most whole row tiles, up to 1,024 rows, that divide the slab — a
    slab is twice the even share, so about half its rows are live, and the
    combine multiplies only the chunks that hold some (cell 11: 896 of
    5,376, three steps of six; cell 8: 384 of 1,152, two of three)."""
    tiles = bound // tm
    return tm * max(d for d in range(1, tiles + 1)
                    if tiles % d == 0 and (tm * d <= 1024 or d == 1))


def _held_compact(x, expert_ids, gates, top_k: int, E: int, bound: int,
                  kernel: str, experts_of):
    """The held share of :func:`_moe_ffn` over slabs of ``bound`` sorted
    rows: ``(out [T, hid] float32, the turns past the first)``.

    ``expert_ids`` ``[T * top_k]`` hold each choice's held expert or ``E``
    (held elsewhere, or no expert), ``gates`` its weight (0 there). One sort
    puts the held choices first, by expert, their weights beside them; turn
    ``t`` takes sorted rows ``t * bound ..``: it gathers their tokens' rows
    of ``x``, plans and runs the grouped products over the part of each
    group that lies in the slab, and adds each token's rows to its sum — a
    product of gate-weighted one-hots ``[T, rows]`` (the gates in the rows'
    dtype) with the rows ``[rows, hid]``, accumulated in float32, chunk by
    chunk (:func:`_combine_chunk`) as far as the slab's live rows go; no
    second sort, no gather back to ``T * top_k`` rows. There are
    ``ceil(held choices / bound)`` turns: none where no choice is held, one
    where the router spreads its choices (the bound is twice the even
    share), as many as it takes otherwise."""
    T, hid = x.shape
    N = expert_ids.shape[0]
    tm = row_tile(bound) if kernel == "pallas" else 8
    assert bound % tm == 0, (bound, tm)
    chunk = _combine_chunk(bound, tm)
    with jax.named_scope("sort"):
        # sorted keys, the choices they came from and their weights; past
        # the end, slabs read choices of no expert and no weight
        pad = -N % bound
        skey, order, sgate = jax.lax.sort(
            (expert_ids.astype(jnp.int32), jnp.arange(N, dtype=jnp.int32),
             gates), num_keys=1)
        skey = jnp.pad(skey, (0, pad), constant_values=E)
        order, sgate = jnp.pad(order, (0, pad)), jnp.pad(sgate, (0, pad))
        # the groups' ends, counted (a bincount is a scatter-add: 186 us at
        # cell 11's 21,120 choices, PERF.md PR 53)
        ends = jnp.sum(expert_ids[None, :] <= jnp.arange(E)[:, None],
                       axis=1, dtype=jnp.int32)
        starts = jnp.concatenate([jnp.zeros(1, jnp.int32), ends[:-1]])
        n_turns = (ends[-1] + bound - 1) // bound

    def turn(t, acc):
        with jax.named_scope("sort"):
            lo = t * bound
            row_e = jax.lax.dynamic_slice(skey, (lo,), (bound,))
            tok = jax.lax.dynamic_slice(order, (lo,), (bound,)) // top_k
            xs = x[tok]                                        # [bound, hid]
            sizes = jnp.clip(ends, lo, lo + bound) \
                - jnp.clip(starts, lo, lo + bound)
            visits = (plan_visits(sizes, bound, tm) if kernel == "pallas"
                      else None)
        with jax.named_scope("experts"):
            ys = experts_of(xs, sizes, visits, jnp.minimum(row_e, E - 1))
        with jax.named_scope("combine"):
            scale = jax.lax.dynamic_slice(sgate, (lo,), (bound,))
            tokens = jnp.arange(T, dtype=tok.dtype)[:, None]

            def add(c, acc):
                at = c * chunk
                live = jax.lax.dynamic_slice(row_e, (at,), (chunk,)) < E
                # rows of no group hold nothing defined
                rows = jnp.where(
                    live[:, None],
                    jax.lax.dynamic_slice(ys, (at, 0), (chunk, hid)), 0)
                onehot = jnp.where(
                    jax.lax.dynamic_slice(tok, (at,), (chunk,))[None, :]
                    == tokens,
                    jnp.where(live, jax.lax.dynamic_slice(
                        scale, (at,), (chunk,)), 0).astype(ys.dtype)[None, :],
                    0)                                         # [T, chunk]
                return acc + jnp.dot(
                    onehot, rows, preferred_element_type=jnp.float32,
                    precision=(jax.lax.Precision.HIGHEST
                               if ys.dtype == jnp.float32 else None))

            n_live = jnp.clip(ends[-1] - lo, 0, bound)
            return jax.lax.fori_loop(0, (n_live + chunk - 1) // chunk, add,
                                     acc)

    out = jax.lax.fori_loop(0, n_turns, turn,
                            jnp.zeros((T, hid), jnp.float32))
    return out, jnp.maximum(n_turns - 1, 0).astype(jnp.int32)



def _mm(x, w):
    """``x @ w`` where ``w`` is a plain array OR a weight-only-int8 dict
    ``{"w8" [K, N] int8, "scale" [1, N] f32}``.

    TPU-native mixed GEMM (parity role: the reference's fp16 x int8 CUTLASS
    mixed_gemm, ``inference/v2/kernels/cutlass_ops/mixed_gemm``): at decode
    shapes the GEMM is weight-READ bound, so int8 storage halves the HBM
    stream. XLA fuses the int8->bf16 convert into the dot's tile pipeline
    (measured v5e-1, M=32: int8 weight stream runs at ~700 GB/s wire rate =
    ~1.4 TB/s bf16-equivalent vs ~750 GB/s for bf16 weights — a true ~1.9x).
    int8 values up to +-127 are exact in bf16; accumulation is fp32 via
    preferred_element_type; the per-output-column scale is applied to the
    fp32 accumulator (valid: scale is constant along K)."""
    if isinstance(w, dict) and "w8" in w:
        o = jax.lax.dot_general(x, w["w8"].astype(x.dtype),
                                (((x.ndim - 1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        return (o * w["scale"]).astype(x.dtype)
    if isinstance(w, dict) and "w4" in w:
        # packed int4 (two values per byte along K — the reference's
        # quantize_intX.cu storage win, /4 vs bf16 at rest): unpack with
        # sign-extending shifts, then the same mixed dot as int8
        from deepspeed_tpu.ops.quantizer import unpack_int4
        wk = unpack_int4(w["w4"], axis=-2)
        o = jax.lax.dot_general(x, wk.astype(x.dtype),
                                (((x.ndim - 1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        return (o * w["scale"]).astype(x.dtype)
    return x @ w


# --------------------------------------------------------------------------- #
# multi-tenant LoRA: paged adapter weights -> per-row grouped delta
# (inference/v2/lora/; docs/SERVING.md "Multi-tenant LoRA")
# --------------------------------------------------------------------------- #

#: projections a LoRA adapter may target (attention only — the S-LoRA /
#: Punica serving pattern; MLP adapters are out of scope for the paged pool)
LORA_TARGETS = ("q", "k", "v", "o")


def lora_target_dims(spec: "RaggedModelSpec",
                     target: str) -> Tuple[int, int]:
    """``(d_in, d_out)`` of one LoRA-targeted base projection."""
    H, Hkv, D = spec.num_heads, spec.num_kv_heads, spec.head_dim
    hid = spec.hidden_size
    dims = {"q": (hid, H * D), "k": (hid, Hkv * D), "v": (hid, Hkv * D),
            "o": (H * D, hid)}
    if target not in dims:
        raise ValueError(f"unknown LoRA target {target!r} "
                         f"(supported: {LORA_TARGETS})")
    return dims[target]


def lora_page_layout(spec: "RaggedModelSpec",
                     targets: Tuple[str, ...]) -> Tuple[int, int, int]:
    """``(elements, in_max, out_max)`` of ONE adapter-weight page.

    A page is one RANK SLICE of a whole adapter — for every layer and every
    targeted projection, column ``j`` of that projection's A matrix (padded
    to ``in_max``) followed by row ``j`` of its B matrix (padded to
    ``out_max``, alpha/rank pre-folded in at registration) — flattened to
    ``[L, nproj, in_max + out_max]`` in ``spec.dtype``. Rank-r adapters own
    r pages; the pool's zero page pads ranks below the dispatch bucket AND
    backs the null adapter, so pad reads contribute exact zeros. Same design
    as a KV page: fixed size from the model spec alone, so the pool is one
    dense device array and the per-row gather is a single take."""
    dims = [lora_target_dims(spec, t) for t in targets]
    in_max = max(d[0] for d in dims)
    out_max = max(d[1] for d in dims)
    return (spec.num_layers * len(targets) * (in_max + out_max),
            in_max, out_max)


def lora_layer_operands(spec: "RaggedModelSpec", targets: Tuple[str, ...],
                        lora_pool, adapter_pt, repeat: int = 1):
    """Per-row adapter pages gathered on device, shaped for the layer scan.

    ``lora_pool`` ``[P + 2, elements]``, ``adapter_pt`` ``[S, RB]`` page
    ids (RB = the engine's pow2 rank bucket; rank padding and pad rows
    point at the pool's zero page) -> ``[L, T, RB, nproj, in_max+out_max]``
    riding the layer scan as xs. ``repeat`` expands sequence rows to token
    rows for the verify step's K+1-rows-per-sequence batch."""
    pages = lora_pool[adapter_pt]                       # [S, RB, E]
    if repeat > 1:
        pages = jnp.repeat(pages, repeat, axis=0)
    T, RB = pages.shape[0], pages.shape[1]
    _, in_max, out_max = lora_page_layout(spec, targets)
    sl = pages.reshape(T, RB, spec.num_layers, len(targets),
                       in_max + out_max)
    return jnp.moveaxis(sl, 2, 0)


def _lora_split(spec: "RaggedModelSpec", targets: Tuple[str, ...], lora_l):
    """One layer's scanned slice ``[T, RB, nproj, io]`` -> ``{target:
    (A [T, RB, d_in], B [T, RB, d_out])}`` for :func:`_lora_mm`."""
    _, in_max, out_max = lora_page_layout(spec, targets)
    out = {}
    for p, t in enumerate(targets):
        din, dout = lora_target_dims(spec, t)
        out[t] = (lora_l[:, :, p, :din],
                  lora_l[:, :, p, in_max:in_max + dout])
    return out


def _lora_mm(x, w, lora, name: str):
    """``_mm(x, w)`` plus the row's grouped LoRA delta ``(x @ A) @ B``.

    The grouped matmul of the multi-tenant decode batch: every token row
    carries ITS OWN adapter's A/B rank slices (gathered by
    :func:`lora_layer_operands`), so one einsum pair serves a batch that
    mixes tenants — no per-adapter dispatch, no batch splitting. Rows bound
    to the zero page (no adapter, rank padding, scratch pad rows) contribute
    exact zeros, which keeps pad rows inert and the null-adapter stream
    byte-identical across batch compositions. fp32 contraction: the rank
    dim is tiny, and it makes the delta independent of the batch's bucket
    shape (the byte-equality gate's requirement)."""
    y = _mm(x, w)
    if lora is None or name not in lora:
        return y
    a, b = lora[name]
    c = jnp.einsum("ti,tri->tr", x.astype(jnp.float32),
                   a.astype(jnp.float32))
    d = jnp.einsum("tr,tro->to", c, b.astype(jnp.float32))
    return y + d.astype(y.dtype)


_QUANT_KEYS = ("wq", "wk", "wv", "wo")
_QUANT_MLP_KEYS = ("w_gate", "w_up", "w_down")


def quantize_weights_int4(weights: Dict) -> Dict:
    """Packed-int4 weight-only serving store (reference parity:
    ``csrc/quantization/quantize_intX.cu`` packed 4-bit). Same tree walk as
    :func:`quantize_weights_int8`, but values quantize to [-7, 7] with
    per-output-column scales and STORE two-per-byte along K
    (``ops/quantizer.pack_int4``) — at-rest HBM is K*N/2 bytes, a measured
    4x under bf16. The matmul unpacks with sign-extending shifts (``_mm``).
    """
    from deepspeed_tpu.ops.quantizer import pack_int4

    def q4(w):
        absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2,
                         keepdims=True)
        scale = jnp.where(absmax > 0, absmax / 7.0, 1.0)
        qv = jnp.clip(jnp.round(w.astype(jnp.float32) / scale),
                      -7, 7).astype(jnp.int8)
        return {"w4": pack_int4(qv, axis=-2),
                "scale": scale.astype(jnp.float32)}

    return _quantize_weight_tree(weights, q4)


def quantize_weights_int8(weights: Dict) -> Dict:
    """Weight-only int8 for the serving weight tree (in place, returns it).

    Symmetric per-output-column int8 over the stacked per-layer matrices
    ``[L, K, N] -> {"w8" int8 [L, K, N], "scale" f32 [L, 1, N]}`` plus the
    untied ``lm_head``; embeddings, norms, and biases stay in the model
    dtype (embeds are row-gathers, not streamed matmuls). Scheme parity:
    the reference quantizer's symmetric mode (``csrc/quantization``)."""
    def q(w):
        absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2,
                         keepdims=True)
        scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
        w8 = jnp.clip(jnp.round(w.astype(jnp.float32) / scale),
                      -127, 127).astype(jnp.int8)
        return {"w8": w8, "scale": scale.astype(jnp.float32)}

    return _quantize_weight_tree(weights, q)


def _layer_stacks(layers):
    """Every stacked tree of ``weights["layers"]``: the one tree, a run's, or
    each of a repeating unit's (:func:`_stack_units`)."""
    if isinstance(layers, tuple):
        for part in layers:
            yield from _layer_stacks(part)
    else:
        yield layers


def _quantize_weight_tree(weights: Dict, q) -> Dict:
    for layers in _layer_stacks(weights["layers"]):
        _quantize_layer_stack(layers, q)
    if "lm_head" in weights and not isinstance(weights["lm_head"], dict):
        weights["lm_head"] = q(weights["lm_head"])
    return weights


def _quantize_layer_stack(layers: Dict, q) -> None:
    for key in _QUANT_KEYS:
        if key in layers and not isinstance(layers[key], dict):
            layers[key] = q(layers[key])
    mlp = layers.get("mlp")
    if isinstance(mlp, dict):
        for key in _QUANT_MLP_KEYS:
            if key in mlp and not isinstance(mlp[key], dict):
                mlp[key] = q(mlp[key])
    moe = layers.get("moe")
    if isinstance(moe, dict):
        # expert stacks [L, E, K, N] — the dominant streamed bytes of an MoE
        # serving step (ADVICE r4: silently skipping them made weight_bits=8
        # a near-no-op on mixtral); scale per (layer, expert, out-column).
        # The router stays fp32 (tiny, feeds top_k).
        for key in _QUANT_MLP_KEYS:
            if key in moe and not isinstance(moe[key], dict):
                moe[key] = q(moe[key])


class _StateRows(NamedTuple):
    """Whose recurrent state each row of a program reads and writes: the
    pass's chunk slots first (``NC`` slots of equal size; None where the
    program has no prompt rows), then one row per decode sequence (None
    where it has none). Slots, modes and token counts as
    ``RaggedBatch.chunk_state_*`` / ``chunk_ntok`` / ``decode_state_slot``."""
    chunk_slot: Any = None      # [NC] int32
    chunk_mode: Any = None      # [NC] int32: 0 zero, 1 the pool, 2 the slot before
    chunk_ntok: Any = None      # [NC] int32
    decode_slot: Any = None     # [S] int32


#: a state slot of this many bytes or more moves by one dynamic slice a row
#: (:func:`_slots_take`), a smaller one by XLA's gather and scatter: of rows
#: of 4 MiB XLA's gather first slices the WHOLE pool into column blocks (2.6
#: GiB of copies a layer at 73 slots; compile, PR 39), of rows of 320 KiB it
#: does not (cell 7, PR 31) — the rule lies between the two sizes seen; a
#: power-retention slot (32.75 MiB) is far on the slices' side of it
_SLOT_SLICE_BYTES = 1 << 20


def _slots_take(flat, rows):
    """``flat[rows]`` of a pool ``[slots, N, E]`` for a few ``rows``: one
    dynamic slice a row (each a contiguous block of the pool) where a row is
    large (``_SLOT_SLICE_BYTES``), else a gather."""
    if flat[0].size * flat.dtype.itemsize < _SLOT_SLICE_BYTES:
        return flat[rows]
    return jnp.concatenate([
        jax.lax.dynamic_slice_in_dim(flat, rows[i], 1) for i in
        range(rows.shape[0])])


def _slots_put(flat, rows, values):
    """``flat.at[rows].set(values)``; where a row is large, one dynamic
    update a row, in order (a row named twice keeps the later value): in
    place on a carried pool."""
    if flat[0].size * flat.dtype.itemsize < _SLOT_SLICE_BYTES:
        return flat.at[rows].set(values)
    for i in range(rows.shape[0]):
        flat = jax.lax.dynamic_update_slice_in_dim(
            flat, values[i:i + 1].astype(flat.dtype), rows[i], 0)
    return flat


class _ChunkRows(NamedTuple):
    """Where a pass's chunk slots lie (:func:`_conv_rows`): ``CT`` prompt
    rows in slots of ``Cs``, each slot's ``mode``, the pool row its state is
    read from and the one it is written to (the dump slot's unless the slot
    is its sequence's last of the pass)."""
    CT: int = 0
    Cs: int = 0
    mode: Any = None
    pool_rows: Any = None
    store_rows: Any = None


def _chunk_extent(rows: _StateRows, T: int) -> Tuple[int, int]:
    """``(CT, Cs)``: the prompt rows of a pass of ``T`` rows and the rows of
    one of its chunk slots."""
    CT = T - (0 if rows.decode_slot is None else rows.decode_slot.shape[0])
    return CT, CT // rows.chunk_slot.shape[0]


def _chunk_rows(rows: _StateRows, NS1: int, l, T: int) -> _ChunkRows:
    """Where the chunk slots of a pass of ``T`` rows lie in the state pools
    of ``NS1`` slots (the dump slot the last) at layer ``l``."""
    if rows.chunk_slot is None:
        return _ChunkRows()
    mode = rows.chunk_mode
    pool_rows = l * NS1 + rows.chunk_slot
    # a sequence's last slot of the pass writes back; the others (and empty
    # slots) write the dump slot
    last = jnp.concatenate([mode[1:] != 2, jnp.ones((1,), bool)])
    store_rows = l * NS1 + jnp.where(last, rows.chunk_slot, NS1 - 1)
    return _ChunkRows(*_chunk_extent(rows, T), mode, pool_rows, store_rows)


def _tail_slots(conv):
    """The tail pool ``[Lm, NS1, (K-1)*8, Wp/8]`` as one list of all layers'
    slots (merging the leading dimensions is a view)."""
    return conv.reshape((-1,) + conv.shape[2:])


def _depthwise_silu(conv_w, bias, dtype):
    """``mix`` of :func:`_conv_rows` for a Mamba or Gated DeltaNet layer: the
    causal depthwise convolution and SiLU. ``conv_w`` ``[K, W]`` float32,
    ``bias`` ``[W]`` float32 or 0.0."""
    K = conv_w.shape[0]
    f32 = jnp.float32

    def conv_act(ext):          # [.., K + n - 1, W] inputs -> [.., n, W]
        n = ext.shape[-2] - (K - 1)
        acc = bias + sum(ext[..., j:j + n, :].astype(f32) * conv_w[j]
                         for j in range(K))
        return jax.nn.silu(acc).astype(dtype)

    return conv_act


def _conv_rows(conv, conv2, l, rows: _StateRows, a, K: int, mix, dtype,
               shift_decode: bool = False):
    """What mixes the rows ``a`` ``[T, W]`` of a layer along the sequence
    over ``K`` taps — ``mix``, which is handed each row behind its ``K - 1``
    predecessors ``[.., K - 1 + n, W]`` and returns ``[.., n, W']``: the
    causal depthwise convolution and SiLU of a layer that keeps a state
    (:func:`_depthwise_silu`; :func:`_mamba_mixer`, :func:`_gdn_mixer`), the
    two convolutions and the shifted value of compressed convolutional
    attention (:func:`_cca_project`) — each row's predecessors read from the
    rows before, the slot before or the tail pool ``conv`` (``conv2``:
    :func:`_tail_slots` of it) at layer ``l``; the chunk slots' new tails are
    written there. A decode row's shift by one token rides with its
    recurrence kernel, or, where no kernel follows (``shift_decode``), is
    written here. Returns ``(the mixed rows [T, W'], conv, the chunk slots'
    _ChunkRows)``."""
    W = a.shape[-1]
    NS1 = conv.shape[1]
    # a slot's tile rows are its K - 1 taps x W channels in order (padded to
    # whole tiles a tap where W is not), so the rows GATHERED from it reshape
    # to [n, K - 1, W] (a small copy — reshaping the pool itself so would lay
    # it out anew, in every layer)
    Wp = 8 * conv.shape[3]
    if Wp == W:
        taps = lambda rows_: conv2[rows_].reshape(-1, K - 1, W)
        as_taps = lambda t: t.reshape((-1,) + conv.shape[2:])
    else:
        taps = lambda rows_: conv2[rows_].reshape(-1, K - 1, Wp)[..., :W]
        as_taps = lambda t: jnp.pad(
            t, ((0, 0), (0, 0), (0, Wp - W))).reshape((-1,) + conv.shape[2:])
    parts, chunk = [], _ChunkRows()
    if rows.chunk_slot is not None:
        CT, Cs = _chunk_extent(rows, a.shape[0])
        a_c = a[:CT].reshape(-1, Cs, W)
        chunk = _chunk_rows(rows, NS1, l, a.shape[0])
        mode, pool_rows, store_rows = chunk[2:]
        tail = jnp.where(
            (mode == 2)[:, None, None],
            jnp.roll(a_c[:, Cs - (K - 1):], 1, axis=0),
            jnp.where((mode == 1)[:, None, None], taps(pool_rows), 0))
        ext = jnp.concatenate([tail.astype(dtype), a_c], axis=1)
        parts.append(mix(ext).reshape(CT, -1))
        new_tail = jax.vmap(lambda e, n: jax.lax.dynamic_slice(
            e, (n, 0), (K - 1, W)))(ext, rows.chunk_ntok)
        conv2 = conv2.at[store_rows].set(
            as_taps(new_tail.astype(conv.dtype)))
    if rows.decode_slot is not None:
        # the rows' tails are read here; their shift by one token rides
        # with the recurrence kernel
        drows = l * NS1 + rows.decode_slot
        ext = jnp.concatenate([taps(drows).astype(dtype),
                               a[chunk.CT:, None]], axis=1)      # [S, K, W]
        parts.append(mix(ext)[:, 0])
        if shift_decode:
            conv2 = conv2.at[drows].set(
                as_taps(ext[:, 1:].astype(conv.dtype)))
    conv = conv2.reshape(conv.shape)
    c = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return c, conv, chunk


def _chunk_states(ssm, rows: _StateRows, chunk: _ChunkRows):
    """For the chunked scan of a pass's prompt rows: which rows hold a token
    ``[CT, 1]``, the pool as a list of slots ``[Lm * NS1, N, E]`` and the
    state each chunk slot starts from (the pool's where ``mode`` is 1, else
    zero); a slot whose ``mode`` is 2 continues the one before it."""
    N, E = ssm.shape[2:]
    live = (jnp.arange(chunk.Cs)[None, :]
            < rows.chunk_ntok[:, None]).reshape(chunk.CT, 1)
    flat = ssm.reshape(-1, N, E)
    h0 = jnp.where((chunk.mode == 1)[:, None, None],
                   _slots_take(flat, chunk.pool_rows), 0.0)
    return live, flat, h0


def _mamba_mixer(spec: "RaggedModelSpec", w, u, state, l, rows: _StateRows):
    """The Mamba mixer on the normed rows ``u`` ``[T, hid]`` of one layer,
    reading and updating its rows' states in the pools ``state = (ssm [Lm,
    NS+1, N, E], conv [Lm, NS+1, (K-1)*8, W/8])`` (float32;
    ragged/state_pool.py) at layer ``l`` of them. Returns ``(out [T, hid],
    ssm, conv)``. ``spec.mamba`` says which recurrence
    (``ops/pallas/ssm.py`` states both):

    - Mamba-1 (Jamba's: RMSNorm on dt, B and C): ``in_proj`` gives the
      convolution's input and the gate; ``x_proj`` makes ``dt``'s low-rank
      input, ``B`` and ``C`` from the convolved rows; the state decays by the
      channel and the state value;
    - Mamba-2 (``"kind": "mamba2"``; granite's, nemotron_h's): ``in_proj``
      gives the gate, the convolution's input — x, B and C together, ``W = E
      + 2 G N`` channels, ``G = n_groups`` pairs of B and C, each shared by
      ``H / G`` heads — and ``dt`` a head; no ``x_proj``, ``dt_proj`` or
      inner norms; the state decays by the head; the gate is followed by an
      RMSNorm over each group's ``E / G`` channels (scope ``ssm/gate_norm``)
      before ``out_proj``.

    What they share is the rows' bookkeeping. Prompt rows run the chunked
    scan slot by slot (scope ``ssm/scan``): a chunk slot starts from zero,
    from the pool or from the slot before it (``rows.chunk_mode``), rows past
    its token count leave the state alone (their ``dt`` is zeroed), and only
    a sequence's last slot of the pass writes the pool. Decode rows are
    segments of one token (``ssm/step``). The convolution reads its ``K - 1``
    predecessors from the rows before, the slot before or the pool's tail.
    ``dt``, the decay, ``h`` and ``y`` are float32, the matrices and the tail
    the model's dtype."""
    m, mw = spec.mamba, w["mamba"]
    ssd = m.get("kind") == "mamba2"
    E, N = m["d_inner"], m["d_state"]
    G = m.get("n_groups", 1) if ssd else 1      # groups of heads sharing B, C
    W = E + 2 * G * N if ssd else E      # channels the convolution runs over
    dtype = spec.dtype
    ssm, conv = state
    conv2 = _tail_slots(conv)
    f32 = jnp.float32
    conv_w, conv_b = mw["conv_w"].astype(f32), mw["conv_b"].astype(f32)

    with jax.named_scope("in_proj"):
        az = _mm(u, mw["in_proj"])
    if ssd:
        z, a, dt_in = az[:, :E], az[:, E:E + W], az[:, E + W:]
    else:
        a, z = az[:, :E], az[:, E:]
    with jax.named_scope("conv"):
        c, conv, chunk = _conv_rows(
            conv, conv2, l, rows, a, conv_w.shape[0],
            _depthwise_silu(conv_w, conv_b, dtype), dtype)
    CT, store_rows = chunk.CT, chunk.store_rows

    if ssd:
        # x, B and C are the convolved rows' three parts; a step size and a
        # decay a head
        Bm, Cm = c[:, E:E + G * N].astype(f32), c[:, E + G * N:].astype(f32)
        if G > 1:       # [T, G, N]: head h reads group h // (H / G)
            Bm, Cm = Bm.reshape(-1, G, N), Cm.reshape(-1, G, N)
        c = c[:, :E]
        dt = jax.nn.softplus(dt_in.astype(f32) + mw["dt_bias"].astype(f32))
        A = -jnp.exp(mw["A_log"].astype(f32))                   # [H]
        chunk_scan = functools.partial(ssd_chunk_scan,
                                       chunk=m.get("chunk", 256))
        decode_step = ssd_decode_step
    else:
        R = m["dt_rank"]
        rbc = _mm(c, mw["x_proj"])
        r = _norm(rbc[:, :R], {"scale": mw["dt_norm"]}, "rms", spec.eps,
                  dtype)
        Bm = _norm(rbc[:, R:R + N], {"scale": mw["b_norm"]}, "rms", spec.eps,
                   dtype).astype(f32)
        Cm = _norm(rbc[:, R + N:], {"scale": mw["c_norm"]}, "rms", spec.eps,
                   dtype).astype(f32)
        dt = jax.nn.softplus(_mm(r, mw["dt_proj"]).astype(f32)
                             + mw["dt_bias"].astype(f32))
        A = -jnp.exp(mw["A_log"].astype(f32))                   # [N, E]
        chunk_scan, decode_step = ssm_chunk_scan, ssm_decode_step
    cf = c.astype(f32)

    ys = []
    if rows.chunk_slot is not None:
        with jax.named_scope("scan"):
            live, flat, h0 = _chunk_states(ssm, rows, chunk)
            y, hT = chunk_scan(jnp.where(live, dt[:CT], 0.0), cf[:CT],
                               Bm[:CT], Cm[:CT], A, h0,
                               (chunk.mode == 2).astype(jnp.int32))
            ssm = _slots_put(flat, store_rows, hT).reshape(ssm.shape)
            ys.append(y)
    if rows.decode_slot is not None:
        with jax.named_scope("step"):
            y, ssm, conv = decode_step(
                ssm, conv, l, rows.decode_slot, dt[CT:], cf[CT:], Bm[CT:],
                Cm[CT:], A, a[CT:])
            ys.append(y)
    y = ys[0] if len(ys) == 1 else jnp.concatenate(ys)
    if ssd:
        y = (y + jnp.repeat(mw["D"].astype(f32), E // m["n_heads"]) * cf) \
            * jax.nn.silu(z.astype(f32))
        with jax.named_scope("gate_norm"):
            # the gate first, then the norm: over all E (one group), or a
            # group's E / G channels at a time
            if G > 1:
                y = y.reshape(-1, G, E // G)
            y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                                  + spec.eps)
            y = y.reshape(-1, E) * mw["norm"].astype(f32)
    else:
        y = (y + mw["D"].astype(f32) * cf) * jax.nn.silu(z.astype(f32))
    with jax.named_scope("out_proj"):
        out = _mm(y.astype(dtype), mw["out_proj"])
    return out, ssm, conv


def _held(x, dtype):
    """``x`` (of ``dtype``) as float32 values of that dtype. Left to itself
    the compiler drops the rounding between a product and what reads its
    result as float32 where it fuses the two (a convert pair: 5e-4 of the
    first delta layer's state in the chip's check, 2e-7 with the rounding
    held; PERF.md, PR 47), and then a prompt row's convolution sees other
    inputs than the tail pool hands a decode row. ``reduce_precision`` is an
    operation of its own and stays."""
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x.astype(jnp.float32), info.nexp,
                                    info.nmant)


def _gdn_mixer(spec: "RaggedModelSpec", w, u, state, l, rows: _StateRows):
    """The Gated DeltaNet mixer (qwen3_next; ``ops/pallas/gdn.py`` states the
    recurrence) on the normed rows ``u`` ``[T, hid]`` of one layer, reading
    and updating its rows' states in the pools ``state`` exactly as
    :func:`_mamba_mixer` does — the same slots, modes, tails and dump slot
    (:func:`_conv_rows`, :func:`_chunk_states`) — at layer ``l`` of them.
    Returns ``(out [T, hid], ssm, conv)``.

    ``in_proj`` gives q, k and v (the convolution's input, ``2 Hk N + E``
    channels) and the output gate ``z``; ``in_ba`` a value head's ``b`` and
    ``a``. After the convolution and SiLU, q and k are L2-normalised a head
    (q scaled by ``N ** -0.5``) and handed on in the model's dtype; ``beta =
    sigmoid(b)`` and the log-decay ``g = -exp(A_log) softplus(a + dt_bias)``
    are float32, as are the state and ``o``. Prompt rows take the chunked
    scan (scope ``gdn/scan``), decode rows the one-token step
    (``gdn/step``). The mixer's own RMSNorm (plain gain, over each value
    head's ``P``) comes FIRST, then the gate ``silu(z)`` (``gdn/gate_norm``):
    Mamba-2's gated norm gates first."""
    m, mw = spec.mamba, w["gdn"]
    E, N, Hv, Hk = m["d_inner"], m["d_state"], m["n_heads"], m["n_key_heads"]
    KD, P = Hk * N, m["d_head"]
    dtype, f32 = spec.dtype, jnp.float32
    ssm, conv = state
    held = functools.partial(_held, dtype=dtype)

    with jax.named_scope("in_proj"):
        az = _mm(u, mw["in_proj"])
        a, z = held(az[:, :2 * KD + E]), az[:, 2 * KD + E:]
        ba = held(_mm(u, mw["in_ba"]))
    with jax.named_scope("conv"):
        conv_w = mw["conv_w"].astype(f32)
        c, conv, chunk = _conv_rows(
            conv, _tail_slots(conv), l, rows, a, conv_w.shape[0],
            _depthwise_silu(conv_w, 0.0, dtype), dtype)
    CT = chunk.CT

    def unit(x, scale):
        x = held(x).reshape(-1, Hk, N)
        x = x * (jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
                 * scale)
        return x.reshape(-1, KD).astype(dtype)

    q, k, v = unit(c[:, :KD], N ** -0.5), unit(c[:, KD:2 * KD], 1.0), \
        c[:, 2 * KD:]
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(mw["A_log"].astype(f32)) * jax.nn.softplus(
        ba[:, Hv:] + mw["dt_bias"].astype(f32))

    ys = []
    if rows.chunk_slot is not None:
        with jax.named_scope("scan"):
            live, flat, h0 = _chunk_states(ssm, rows, chunk)
            y, hT = gdn_chunk_scan(
                q[:CT], k[:CT], v[:CT], jnp.where(live, g[:CT], 0.0),
                jnp.where(live, beta[:CT], 0.0), h0,
                (chunk.mode == 2).astype(jnp.int32),
                chunk=m.get("chunk", 64))
            ssm = _slots_put(flat, chunk.store_rows, hT).reshape(ssm.shape)
            ys.append(y)
    if rows.decode_slot is not None:
        with jax.named_scope("step"):
            y, ssm, conv = gdn_decode_step(
                ssm, conv, l, rows.decode_slot, g[CT:], beta[CT:], q[CT:],
                k[CT:], v[CT:], a[CT:])
            ys.append(y)
    y = ys[0] if len(ys) == 1 else jnp.concatenate(ys)
    with jax.named_scope("gate_norm"):
        y = y.reshape(-1, Hv, P)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                              + spec.eps) * mw["norm"].astype(f32)
        y = y.reshape(-1, E) * jax.nn.silu(z.astype(f32))
    with jax.named_scope("out_proj"):
        out = _mm(y.astype(dtype), mw["out_proj"])
    return out, ssm, conv


def _pr_mixer(spec: "RaggedModelSpec", w, u, state, l, rows: _StateRows,
              positions):
    """The power-retention mixer (brumby; ``ops/pallas/power_retention.py``
    states both forms) on the normed rows ``u`` ``[T, hid]`` of one layer at
    ``positions`` ``[T]``, reading and updating its rows' states in the pool
    ``state[0]`` (``ssm [Lm, NS+1, N, D]`` float32: ``Hk`` heads' ``S`` and a
    normaliser a head; the tail pool ``state[1]`` is of zero size and passes
    through) at layer ``l``, by :func:`_mamba_mixer`'s bookkeeping of slots,
    modes and the dump slot (:func:`_chunk_rows`, :func:`_chunk_states`).
    Returns ``(out [T, hid], ssm, conv)``.

    q, k and v are three projections of the rows; q and k take an RMSNorm a
    head and the rotation by position (``pr/qk_norm_rope``), and all three
    reach the recurrence as values of the model's dtype; the gate is
    ``log_sigmoid`` of a fourth projection plus a bias, one a KV head, in
    float32 (``pr/gate``). Prompt rows take the chunked scan (``pr/scan``),
    decode rows the one-token step (``pr/step``); a row that holds no token
    has its key and its log-gate zeroed, so it neither decays nor writes."""
    m, mw = spec.mamba, w["pr"]
    H, Hk, D = spec.num_heads, spec.num_kv_heads, spec.head_dim
    dtype, f32 = spec.dtype, jnp.float32
    ssm, conv = state

    with jax.named_scope("qkv_proj"):
        # (values of their own behind a barrier: _transformer_layer's note)
        q, k, v = jax.lax.optimization_barrier((
            _mm(u, mw["wq"]), _mm(u, mw["wk"]), _mm(u, mw["wv"])))
    with jax.named_scope("qk_norm_rope"):
        # (the norm's result HELD at the model's dtype: left to itself the
        # compiler drops the rounding between the norm and the rotation it
        # fuses it with — 7e-4 of the first layer's state in the chip's
        # check, PR 54 — and a program that fuses otherwise would write
        # other keys into the same state)
        def normed(x, heads, gain):
            x = _held(_norm(x.reshape(-1, heads, D), {"scale": gain}, "rms",
                            spec.eps, dtype), dtype)
            return _rope_flat(x, positions, spec.rope_theta, None).astype(
                dtype).reshape(-1, heads * D)

        q, k = normed(q, H, mw["q_norm"]), normed(k, Hk, mw["k_norm"])
    with jax.named_scope("gate"):
        lg = jax.nn.log_sigmoid(_held(_mm(u, mw["wg"]), dtype)
                                + mw["g_bias"].astype(f32))

    chunk = _chunk_rows(rows, ssm.shape[1], l, u.shape[0])
    CT = chunk.CT
    ys = []
    if rows.chunk_slot is not None:
        with jax.named_scope("scan"):
            live, flat, h0 = _chunk_states(ssm, rows, chunk)
            y, hT = pr_chunk_scan(
                q[:CT], jnp.where(live, k[:CT], 0).astype(dtype), v[:CT],
                jnp.where(live, lg[:CT], 0.0), h0,
                (chunk.mode == 2).astype(jnp.int32),
                chunk=m.get("chunk", 128), eps=m["eps"])
            ssm = _slots_put(flat, chunk.store_rows, hT).reshape(ssm.shape)
            ys.append(y)
    if rows.decode_slot is not None:
        with jax.named_scope("step"):
            y, ssm = pr_decode_step(ssm, l, rows.decode_slot, lg[CT:], q[CT:],
                                    k[CT:], v[CT:], eps=m["eps"])
            ys.append(y)
    y = ys[0] if len(ys) == 1 else jnp.concatenate(ys)
    with jax.named_scope("out_proj"):
        out = _mm(y.astype(dtype), mw["wo"])
    return out, ssm, conv


def _cca_mix(spec: "RaggedModelSpec", cw):
    """``mix`` of :func:`_conv_rows` for compressed convolutional attention:
    of the rows' channels ``[s = q and k of every head | z]`` behind their
    ``taps`` predecessors, ``[y | z of the token before]`` in float32 —
    ``m_t = w0[0] s_{t-1} + w0[1] s_t + b0`` (depthwise), ``y_t = M[0]
    m_{t-1} + M[1] m_t + b1`` (a ``[d, d]`` block a head a tap), no
    activation. Before a sequence's first token the INPUT is zero (the tail
    a chunk slot starts from), so ``m_{-1} = b0``. ``m`` is rounded to the
    model's dtype, as a convolution's output is, and the blocks' products
    accumulate in float32 (float32 operands that hold the model's dtype's
    values: at the chip's default precision one pass of the MXU, exact)."""
    c, D, f32 = spec.cca, spec.head_dim, jnp.float32
    C, taps = c["conv_dim"], c["taps"]
    w0, b0 = cw["conv0_w"].astype(f32), cw["conv0_b"].astype(f32)
    w1, b1 = cw["conv1_w"].astype(f32), cw["conv1_b"].astype(f32)
    K0, K1 = w0.shape[0], w1.shape[0]

    def mix(ext):               # [B, taps + n, C + D] -> [B, n, C + D]
        n = ext.shape[-2] - taps
        s = ext[..., :C].astype(f32)
        nm = n + K1 - 1
        m = b0 + sum(s[:, j:j + nm] * w0[j] for j in range(K0))
        m = _held(m, spec.dtype).reshape(m.shape[:-1] + (C // D, D))
        y = sum(jnp.einsum("bthi,hio->btho", m[:, j:j + n], w1[j])
                for j in range(K1))
        y = y.reshape(y.shape[:2] + (C,)) + b1
        return jnp.concatenate(
            [y, ext[:, taps - 1:taps - 1 + n, C:].astype(f32)], axis=-1)

    return mix


def _cca_project(spec: "RaggedModelSpec", w, u, positions, conv, l,
                 rows: _StateRows):
    """Compressed convolutional attention (zaya) up to the kernel, on the
    normed rows ``u`` ``[T, hid]`` of one layer: ``(q [T, H, D], k [T, Hk,
    D], v [T, Hk, D], conv)``, ``k`` and ``v`` what the pages hold of a
    token. The rows' predecessors are read from the tail pool ``conv``
    ``[L, NS + 1, taps * 8, W / 8]`` (float32; ragged/state_pool.py) at
    layer ``l`` of it and the new tails written there, the rows' bookkeeping
    :func:`_conv_rows`'s — the same slots, modes and dump slot as a layer
    that keeps a state.

    Scope ``proj``: ``in_proj`` gives ``[qp | kp | z | v1]`` (:func:`adapt_zaya`).
    Scope ``mix``: the two convolutions over ``s = [qp ; kp]`` (:func:`_cca_mix`);
    the q-k mean from the PRE-convolution values, ``q_j = y^q_j + (qp_j +
    kp_{j // G}) / 2``, ``k_i = y^k_i + (kp_i + mean_{j in i} qp_j) / 2``;
    each head normed to ``sqrt(D)`` (an RMS norm without a gain), ``k`` times
    the head's temperature; the first ``rotary_dim`` values of each head
    rotated; key/value head 0's value the token's own ``v1``, head 1's ``z``
    of the token BEFORE. Float32 from the products' (rounded) results to q
    and k, which are rounded once."""
    c, cw = spec.cca, w["cca"]
    H, Hk, D = spec.num_heads, spec.num_kv_heads, spec.head_dim
    G, C, Wt = H // Hk, c["conv_dim"], c["tail_channels"]
    dtype, f32 = spec.dtype, jnp.float32
    with jax.named_scope("proj"):
        az = _mm(u, cw["in_proj"])
        a, v1 = _held(az[:, :Wt], dtype), az[:, Wt:]
    with jax.named_scope("mix"):
        mixed, conv, _ = _conv_rows(
            conv, _tail_slots(conv), l, rows, a, c["taps"] + 1,
            _cca_mix(spec, cw), dtype, shift_decode=True)
        qp = a[:, :H * D].reshape(-1, Hk, G, D)
        kp = a[:, H * D:C].reshape(-1, Hk, D)
        q = mixed[:, :H * D].reshape(-1, Hk, G, D) \
            + (qp + kp[:, :, None]) * 0.5
        k = mixed[:, H * D:C].reshape(-1, Hk, D) \
            + (kp + jnp.mean(qp, axis=2)) * 0.5
        unit = lambda x: x * jax.lax.rsqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + spec.eps)
        q = unit(q).reshape(-1, H, D)
        k = unit(k) * cw["temp"].astype(f32)[:, None]
        q = _rope_flat(q, positions, spec.rope_theta,
                       spec.rotary_dim).astype(dtype)
        k = _rope_flat(k, positions, spec.rope_theta,
                       spec.rotary_dim).astype(dtype)
        v = jnp.stack([v1, mixed[:, C:].astype(dtype)], axis=1)
    return q, k, v, conv


def _mla_project(spec: "RaggedModelSpec", w, h1, positions):
    """The projections of latent attention on the normed rows ``h1``:
    ``(q_nope [N, H, nope], q_rope [N, H, rope] rotated, the rows' latent
    rows [N, W])`` — a row is what the pool holds of a token: ``c_kv`` after
    its norm, the shared rotary key after rotation, zeros up to ``W``."""
    m, H = spec.mla, spec.num_heads
    R, dn, dr = m["kv_lora_rank"], m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    dtype = spec.dtype
    with jax.named_scope("q_proj"):
        cq = _norm(_mm(h1, w["wqa"]), {"scale": w["q_a_norm"]}, "rms",
                   spec.eps, dtype)
        # the [N, H * (nope + rope)] result stays a value of its own, as
        # the q/k/v results below do (PR 30): left to fold the reshape to
        # heads and the rotation into the dot's output layout, the TPU
        # compiler staged layer l of wqb (18 MiB) and copied it transposed in
        # every layer, 1.6 ms of a 22.7 ms decode step (PR 33)
        q = jax.lax.optimization_barrier(_mm(cq, w["wqb"])).reshape(
            -1, H, dn + dr)
        q_rope = _rope_flat(q[..., dn:], positions, spec.rope_theta, None)
    with jax.named_scope("kv_latent"):
        kva = _mm(h1, w["wkva"])
        ckv = _norm(kva[:, :R], {"scale": w["kv_a_norm"]}, "rms", spec.eps,
                    dtype)
        k_rope = _rope_flat(kva[:, None, R:], positions, spec.rope_theta,
                            None)[:, 0]
        lat = jnp.concatenate(
            [ckv, k_rope,
             jnp.zeros((ckv.shape[0], latent_width(spec) - R - dr), dtype)],
            axis=-1)
    if "index" in m:
        return (q[..., :dn], q_rope, lat) + _index_project(
            spec, w["index"], h1, cq, positions)
    return q[..., :dn], q_rope, lat


def _index_project(spec: "RaggedModelSpec", wi, h1, cq, positions):
    """The indexer's projections (scope ``index/project``): ``(index
    queries [N, Hi, Di'] from the normed query latent, the heads' signed
    weights [N, Hi] in float32, the rows' index keys [N, Di'])``. A key is
    what the index pool holds of a token: ``h1 W_k`` after its LayerNorm,
    its first ``rope_dim`` values rotated (so are the queries'), zeros up to
    the pool's width ``Di'``."""
    ix = spec.mla["index"]
    Hi, Di, dr = ix["heads"], ix["head_dim"], ix["rope_dim"]
    dtype = spec.dtype

    def rotated(x):
        return jnp.concatenate(
            [_rope_flat(x[..., :dr], positions, spec.rope_theta, None),
             x[..., dr:],
             jnp.zeros(x.shape[:-1] + (index_width(spec) - Di,), x.dtype)],
            axis=-1).astype(dtype)

    with jax.named_scope("index"), jax.named_scope("project"):
        q = rotated(jax.lax.optimization_barrier(
            _mm(cq, wi["wq"])).reshape(-1, Hi, Di))
        k = rotated(_norm(_mm(h1, wi["wk"]), {"scale": wi["k_norm"],
                                              "bias": wi["k_bias"]},
                          "layer", ix["eps"], dtype)[:, None])[:, 0]
        wts = jnp.dot(h1, wi["ww"], preferred_element_type=jnp.float32) \
            * (Hi ** -0.5 * Di ** -0.5)
    return q, wts, k


def _transformer_layer(spec: "RaggedModelSpec", w, x, positions, attend,
                       lora=None, experts=None, l=0, tails=None):
    """Shared per-layer transformer body for BOTH the ragged forward (put
    passes) and the fused decode step — one implementation so the two
    paths cannot diverge.  ``attend(q, k, v) -> (attn_raw [N, H, D],
    *state)`` performs the KV page write + attention for its pass shape;
    ``state`` is the caller's carried cache state (pools, or pools + scale
    pools for int8 KV). ``lora`` (``_lora_split`` output, or None) adds each
    row's grouped adapter delta to the targeted attention projections.
    ``experts`` are the whole expert stacks ``_split_expert_stacks`` kept out
    of the scanned ``w``, and ``l`` this layer's index in them.
    ``tails`` (:func:`_tail_args`), for a layer that keeps a convolution
    tail beside its pages: ``(the tail pool, the rows' state slots, the
    layer's index in that pool)``; the new tail pool is then the last value
    of ``state_tuple``. ``x`` is the residual stream, or for a model whose
    router carries a state from layer to layer the pair ``(stream, router
    state [N, R] float32)`` (:func:`_router_stream`), and comes back as it
    came. Returns ``(x_out, state_tuple)``.
    """
    H, Hkv, D = spec.num_heads, spec.num_kv_heads, spec.head_dim
    dtype = spec.dtype
    state = ()
    r = turns = live = None
    if isinstance(x, tuple):
        x, r, turns, live = x
    if spec.block == "ffn":
        pass        # the layer is its FFN alone: no mixer, ``attend`` unused
    elif spec.mamba is not None:
        # a layer whose mixer is no attention: ``attend(normed rows) ->
        # (mixer output [N, hid], *state)`` runs :func:`_mamba_mixer` with
        # the caller's rows and carried state pools (:func:`_gdn_mixer` for a
        # Gated DeltaNet layer, :func:`_pr_mixer` for power retention, each
        # under a scope of its own)
        kind = spec.mamba.get("kind")
        with jax.named_scope(kind if kind in ("gdn", "pr") else "ssm"):
            h1 = _norm(x, w["ln1"], spec.norm, spec.eps, dtype,
                       spec.norm_plus_one)
            attn_out, *state = attend(h1)
    elif spec.mla is not None:
        # latent attention: ``attend(q_nope [N, H, nope], q_rope [N, H,
        # rope], latent rows [N, W]) -> (attention output [N, H * v],
        # *state)`` writes the rows into the latent pages and attends in the
        # form its program uses (expanded or absorbed: ragged_mla.py); with
        # an indexer it is handed the index queries, weights and keys too
        with jax.named_scope("attn"), jax.named_scope("mla"):
            h1 = _norm(x, w["ln1"], spec.norm, spec.eps, dtype,
                       spec.norm_plus_one)
            attn_raw, *state = attend(*_mla_project(spec, w, h1, positions))
            attn_out = _mm(attn_raw, w["wo"])
    elif spec.cca is not None:
        # compressed convolutional attention: what is attended over exists
        # only after the mixing; ``attend(q, k, v)`` is then any attention
        # layer's (the page write and the kernel of the caller's program)
        with jax.named_scope("attn"), jax.named_scope("cca"):
            h1 = _norm(x, w["ln1"], spec.norm, spec.eps, dtype,
                       spec.norm_plus_one)
            conv, rows, l_tail = tails
            q, k, v, conv = _cca_project(spec, w, h1, positions, conv,
                                         l_tail, rows)
            with jax.named_scope("attn_full"):
                attn_raw, *state = attend(q, k, v)
            state.append(conv)
            with jax.named_scope("out"):
                attn_out = _mm(attn_raw.reshape(-1, H * D), w["wo"])
    else:
        # the two halves carry scopes: a device trace tells the layer's
        # attention (projections, rope, KV write, kernel) from its FFN
        with jax.named_scope("attn"):
            h1 = _norm(x, w["ln1"], spec.norm, spec.eps, dtype, spec.norm_plus_one)
            # The projections' [N, out] results stay values of their own. Left
            # to fold the reshape to heads (and the rotation after it) into the
            # dot's output layout, the TPU compiler asks for a transposed weight:
            # it then reads layer l of wq, wk and wv out of the stack into
            # on-chip memory and copies each transposed there before a dot runs
            # (2.3 ms of a 12 ms Mistral-7B decode step on a v5e; 1.15 fused).
            # Behind the barrier the dot's fusion takes the stack and l, as wo's
            # and the FFN's do.
            q, k, v = jax.lax.optimization_barrier((
                _lora_mm(h1, w["wq"], lora, "q"), _lora_mm(h1, w["wk"], lora, "k"),
                _lora_mm(h1, w["wv"], lora, "v")))
            q = q.reshape(-1, H, D)
            k = k.reshape(-1, Hkv, D)
            v = v.reshape(-1, Hkv, D)
            if "bq" in w:
                q = q + w["bq"].reshape(H, D)
                k = k + w["bk"].reshape(Hkv, D)
                v = v + w["bv"].reshape(Hkv, D)
            if "q_norm" in w:       # RMSNorm over each head's values (afmoe)
                q = _norm(q, {"scale": w["q_norm"]}, "rms", spec.eps, dtype,
                          spec.norm_plus_one)
                k = _norm(k, {"scale": w["k_norm"]}, "rms", spec.eps, dtype,
                          spec.norm_plus_one)
            if spec.rope_theta is not None:
                q = _rope_flat(q, positions, spec.rope_theta, spec.rotary_dim)
                k = _rope_flat(k, positions, spec.rope_theta, spec.rotary_dim)

            # KV page write + kernel, by the layer's kind of attention
            with jax.named_scope("attn_full" if spec.window is None
                                 else "attn_window"):
                attn_raw, *state = attend(q, k, v)
            attn_raw = attn_raw.reshape(-1, H * D)
            if "wg" in w:           # output gate from the normed input (afmoe)
                with jax.named_scope("gate"):
                    gate = jax.nn.sigmoid(_mm(h1, w["wg"]).astype(jnp.float32))
                    attn_raw = (attn_raw * gate).astype(dtype)
            attn_out = _lora_mm(attn_raw, w["wo"], lora, "o")
            if "bo" in w:
                attn_out = attn_out + w["bo"]
            if "ln1_post" in w:     # sandwich norm: the branch's output, normed
                attn_out = _norm(attn_out, w["ln1_post"], spec.norm, spec.eps,
                                 dtype, spec.norm_plus_one)

    # a branch joins the residual stream times ``residual_scale`` (granite),
    # in float32 so that the stream is rounded once
    scaled = spec.residual_scale not in (None, 1.0)
    join = (lambda x, out, i=0: (x.astype(jnp.float32) + spec.residual_scale
                                 * out.astype(jnp.float32)).astype(dtype)) \
        if scaled else (lambda x, out, i=0: x + out)
    if "res_scale" in w:
        # .. or by learned vectors (zaya): a scale and a bias a channel on
        # the stream and on the branch, rows i and i + 1 of the layer's four
        res_a = w["res_scale"].astype(jnp.float32)
        res_c = w["res_bias"].astype(jnp.float32)
        join = lambda x, out, i=0: (
            (res_a[i] * x.astype(jnp.float32) + res_c[i])
            + (res_a[i + 1] * out.astype(jnp.float32) + res_c[i + 1])
        ).astype(dtype)
    stream = lambda x: x if r is None and turns is None else (x, r, turns,
                                                              live)
    if spec.block == "mixer":       # one block a layer: no FFN follows
        return stream(join(x, attn_out).astype(dtype)), tuple(state)
    if spec.block == "ffn":         # .. or none went before: its one norm
        mlp_in = _norm(x, w["ln1"], spec.norm, spec.eps, dtype,
                       spec.norm_plus_one)
    elif spec.parallel_block:
        mlp_in = (_norm(x, w["ln2"], spec.norm, spec.eps, dtype,
                        spec.norm_plus_one)
                  if spec.parallel_dual_norm else h1)
    else:
        x = join(x, attn_out)
        mlp_in = _norm(x, w["ln2"], spec.norm, spec.eps, dtype,
                       spec.norm_plus_one)

    with jax.named_scope("ffn"):
        if spec.moe is not None:
            routed = None
            if spec.moe.get("router") == "mlp":
                with jax.named_scope("moe_ffn"), jax.named_scope("router"):
                    *routed, r = moe_route_mlp(mlp_in, w["moe"], spec.moe, r,
                                               spec.eps)
            mlp_out = _moe_ffn(mlp_in, {**w["moe"], **(experts or {})},
                               spec.moe["top_k"], dtype, l, routing=spec.moe,
                               routed=routed, turns=turns, live=live)
            if turns is not None:
                mlp_out, turns = mlp_out
        else:
            m = w["mlp"]
            if spec.activation in ("swiglu", "geglu"):
                gate_act = jax.nn.silu if spec.activation == "swiglu" else jax.nn.gelu
                hmid = gate_act(_mm(mlp_in, m["w_gate"])) * _mm(mlp_in, m["w_up"])
            else:
                act = _plain_act(spec.activation)
                hmid = _mm(mlp_in, m["w_up"])
                if "b_up" in m:
                    hmid = hmid + m["b_up"]
                hmid = act(hmid)
            mlp_out = _mm(hmid, m["w_down"])
            if "b_down" in m:
                mlp_out = mlp_out + m["b_down"]
        if "ln2_post" in w:
            mlp_out = _norm(mlp_out, w["ln2_post"], spec.norm, spec.eps,
                            dtype, spec.norm_plus_one)

    if spec.parallel_block:
        x = join(x, attn_out + mlp_out) if scaled else x + attn_out + mlp_out
    else:
        x = join(x, mlp_out, 2)
    return stream(x.astype(dtype)), tuple(state)


def _router_stream(spec: "RaggedModelSpec", x, weights=None, live=None):
    """What the layer loop carries first: the residual stream ``x`` ``[T,
    hid]``, or ``(x, r, turns, live)`` — ``r`` the router's state ``[T, R]``
    (float32, zero before the first layer) where the router hands one from
    layer to layer (:func:`moe_route_mlp`), else None; ``turns`` an int32
    count of a held share's turns past the first, summed over the layers, in
    a program that hands its ``weights`` (the prefill passes and the decode
    step; not the verify step) and whose rows give a held share a bound
    (:func:`pass_held_rows_bound`) — which lets its MoE layers take the
    compact path of :func:`_moe_ffn` — else None; beside such a count, what
    ``live()`` gives: ``[T]``, the rows that hold a token, where the program
    can tell (a pass's padding is routed like any row and read by nobody: on
    the compact path it asks no expert), else None."""
    moe = spec.moe or {}
    r = turns = None
    if moe.get("router") == "mlp":
        r = jnp.zeros((x.shape[0], moe["router_hidden"]), jnp.float32)
    if weights is not None and pass_held_rows_bound(
            spec, weights, x.shape[0]) is not None:
        turns = jnp.zeros((), jnp.int32)
    if r is None and turns is None:
        return x
    return x, r, turns, None if turns is None or live is None else live()


def _pass_rows_live(b, slot_size: int, decode_rows: bool):
    """Which rows of a pass hold a token: of its chunk slots ``[NC * Cs]``,
    and with ``decode_rows`` of the decode rows after them ``[S]``."""
    live = (jnp.arange(slot_size)[None, :]
            < b["chunk_ntok"][:, None]).reshape(-1)
    if decode_rows:
        live = jnp.concatenate([live, b["decode_ctx_lens"] > 0])
    return live


def _stream_out(x):
    """The residual stream out of what :func:`_router_stream` made."""
    return x[0] if isinstance(x, tuple) else x


def _stream_turns(x) -> Tuple:
    """What a program returns after its three results: nothing, or its held
    share's turns past the first (``(turns,)``) where it counted them."""
    return (x[2],) if isinstance(x, tuple) and x[2] is not None else ()


def _tail_args(rs: "RaggedModelSpec", st, rows, l) -> Dict[str, Any]:
    """``_transformer_layer``'s ``tails`` for a layer of run ``rs`` at index
    ``l`` of the pools, out of the program's state pools ``st = (ssm,
    conv)``; nothing for a layer that keeps no tail beside its pages."""
    return {} if rs.cca is None else {"tails": (st[1], rows, l)}


def _tail_kept(st, tail):
    """The state pools with the tail pool ``_transformer_layer`` handed back
    (``tail``: what it returned past the attention's own state)."""
    return (st[0], *tail) if tail else st


def _embed_in(spec: "RaggedModelSpec", weights, tokens, positions):
    """Token (+ learned position) embedding with the Gemma sqrt(hidden)
    normaliser — fp32 round-trip matches models/llama.py ``_trunk``."""
    x = weights["embed"][tokens]
    if spec.learned_pos:
        x = x + weights["pos_embed"][positions + spec.pos_offset]
    if spec.embed_norm:
        x = _norm(x.astype(spec.dtype), weights["embed_norm"], spec.norm,
                  spec.eps, spec.dtype, spec.norm_plus_one)
    if spec.embed_scale_by_sqrt_dim:
        x = x.astype(jnp.float32) * (spec.hidden_size ** 0.5)
    if spec.embed_scale not in (None, 1.0):
        x = x.astype(jnp.float32) * spec.embed_scale
    return x.astype(spec.dtype)


def _unembed(spec: "RaggedModelSpec", weights, xs):
    """Final-hidden rows -> fp32 logits (tied or untied head, optional bias)."""
    if spec.tied_lm_head:
        logits = xs.astype(jnp.float32) @ weights["embed"].astype(jnp.float32).T
    else:
        logits = _mm(xs, weights["lm_head"]).astype(jnp.float32)
    if spec.head_bias:
        logits = logits + weights["lm_head_bias"].astype(jnp.float32)
    if spec.logits_scale not in (None, 1.0):
        logits = logits * spec.logits_scale
    return logits


def _kv_write_rows(dest_tok, Hkv, bs):
    """Flat K and V row destinations in the combined head-major pool
    [L*NB*2*Hkv*bs, D] for LAYER-GLOBAL token indices ``dest_tok``
    (global_page * bs + slot): K row ((g*2 + 0)*Hkv + h)*bs + slot, V row
    ((g*2 + 1)*Hkv + h)*bs + slot. Sentinel dest (>= pool tokens) maps past
    the pool and drops."""
    page_g = dest_tok // bs
    h = jnp.arange(Hkv)[None, :]
    slot = (dest_tok % bs)[:, None]
    k_rows = ((page_g[:, None] * 2 + 0) * Hkv + h) * bs + slot
    v_rows = ((page_g[:, None] * 2 + 1) * Hkv + h) * bs + slot
    return jnp.concatenate([k_rows.reshape(-1), v_rows.reshape(-1)])


def _kv_page_write(kvp, k, v, dest_tok, Hkv, bs):
    """Scatter of new K/V rows into the FLAT combined head-major paged cache
    [L*NB*2*Hkv*bs, D]; out-of-range dest rows (padding sentinels) drop.

    The flat-rows-with-layer-offset layout is the load-bearing design choice:
    the pool rides the layer scan as CARRY and this scatter is its only
    consumer, so XLA updates the (hundreds of MB) pool in place. The earlier
    per-layer layout — pools as scan xs/ys with a per-layer dynamic-slice +
    scatter + re-stack — materialised two full pool copies per pass and was
    the single largest cost in the decode step (measured ~5 ms of a 16 ms
    step at 0.55B/32 seqs on v5e; see docs/ROUND3_NOTES.md).

    XLA prices the scatter by the index — a row a KV head, K and V, about 70
    ns each on a v5e whatever the bytes — so rows that lie in consecutive
    slots go through :func:`_kv_run_write` (PR 62) and this is left with
    what is ONE row a sequence or runs on no chip's main path: a paged
    pass's decode rows, the verify step's ``k + 1`` rows (no cell
    speculates), every write of a pool :func:`_kv_run_write` turns away (a
    head width that is no whole number of lane tiles) and, as
    ``_kv_page_write_quant``, int8 pools (their scale tiles want a writer of
    their own)."""
    T = dest_tok.shape[0]
    rows = _kv_write_rows(dest_tok, Hkv, bs)
    new = jnp.concatenate([k.reshape(T * Hkv, -1), v.reshape(T * Hkv, -1)])
    return kvp.at[rows].set(new.astype(kvp.dtype), mode="drop")


def kv_write_run_group(kv, n: int, tp: int = 1) -> Optional[int]:
    """The slots a step of the run writer takes for runs of ``n`` rows into
    the pool ``kv`` (as a program is handed it: pages ``[L, NB, 2, Hkv, bs,
    D]``, with an int8 pool's scales or a state pool beside them), or None
    where the rows keep the row scatter: an int8 pool, a pool sharded over
    ``tp`` chips (the scatter partitions by itself, a kernel would want a
    shard_map), or what ``paged_attention.kv_run_group`` turns away. The
    choice reads the pool and the mesh, nothing else — the programs ask it
    as they trace, the engine as it counts rows (``serve/kv_write/*``)."""
    pages, sc = _kv_unpack(_state_unpack(kv)[0])
    return None if sc is not None or tp > 1 else kv_run_group(pages, n)


def _run_write_plan(kv, tables, pos0, count, n: int, tp: int,
                    aligned: bool = False) -> Optional[KvRunPlan]:
    """The run writer's plan (``paged_attention.kv_run_plan``: made once a
    program, outside the scan over layers) for runs of ``n`` rows —
    ``count[r]`` of them from position ``pos0[r]`` through ``tables[r]`` (no
    layer's offset; ``aligned``: every ``pos0`` a multiple of ``n``) — into
    the pool ``kv``, or None where it keeps the row scatter
    (:func:`kv_write_run_group`)."""
    group = kv_write_run_group(kv, n, tp)
    if group is None:
        return None
    bs = _kv_unpack(_state_unpack(kv)[0])[0].shape[4]
    return kv_run_plan(tables, pos0, count, n, group, bs, aligned)


def _kv_run_write(kvp, k, v, l, plan: KvRunPlan, pool_shape):
    """The planned runs' rows (``k`` / ``v`` ``[R * n, Hkv, D]``) to layer
    ``l``'s pages of the flat pool the scan carries — bit for bit what
    :func:`_kv_page_write` leaves, by ``paged_kv_run_write``: the pool goes
    through the kernel aliased, as through the scatter."""
    L, NB, _, Hkv, bs, D = pool_shape
    kv5 = paged_kv_run_write(kvp.reshape(L * NB, 2, Hkv, bs, D), k, v, plan,
                             l * NB)
    return kv5.reshape(-1, D)


def _scale_dest(rows, Hkv, bs):
    """Value-row index [*, in L*NB*2*Hkv*bs] -> flat index into the TILED
    scale pool [L*NB*R8*128]: page r8*128-strided, in-page offset = the flat
    scale index (kv*Hkv*bs + h*bs + t). OOB value rows map OOB."""
    hb2 = 2 * Hkv * bs
    r8 = _scale_tile_rows(Hkv, bs)
    return (rows // hb2) * (r8 * 128) + rows % hb2


def _kv_page_write_quant(kvp, sc, k, v, dest_tok, Hkv, bs):
    """int8 variant of :func:`_kv_page_write`: quantize the new rows on
    append (per token-head) and scatter values + scales. ``sc`` is the FLAT
    view of the tiled at-rest scale pool ([L*NB*R8*128] f32)."""
    T = dest_tok.shape[0]
    rows = _kv_write_rows(dest_tok, Hkv, bs)
    kq, ksc = kv_quantize_rows(k)                              # [T,Hkv,D]/[T,Hkv]
    vq, vsc = kv_quantize_rows(v)
    new = jnp.concatenate([kq.reshape(T * Hkv, -1), vq.reshape(T * Hkv, -1)])
    news = jnp.concatenate([ksc.reshape(-1), vsc.reshape(-1)])
    kvf = kvp.at[rows].set(new, mode="drop")
    scf = sc.at[_scale_dest(rows, Hkv, bs)].set(news, mode="drop")
    return kvf, scf


def _page_plan_gather(k, v, page_rows, page_fill, bs):
    """Gather the page plan's token windows: -> K/V [PW, Hkv, bs, D]."""
    CT = k.shape[0]
    j = jnp.arange(bs, dtype=jnp.int32)
    rows = jnp.minimum(page_rows[:, None] + j[None, :], CT - 1)     # [PW, bs]
    valid = j[None, :] < page_fill[:, None]                         # [PW, bs]
    kg = jnp.where(valid[..., None, None], k[rows], 0)              # [PW,bs,Hkv,D]
    vg = jnp.where(valid[..., None, None], v[rows], 0)
    return jnp.moveaxis(kg, 2, 1), jnp.moveaxis(vg, 2, 1)


def _page_plan_tgt(page_ids, l, NB, L, Hkv):
    """Combined-pool [L*NB*2*Hkv, bs, D] head-row targets for a page plan:
    K rows (g*2+0)*Hkv + h, V rows (g*2+1)*Hkv + h. Sentinel pages (id >=
    NB) go out of range GLOBALLY, not into the next layer's pages."""
    page_g = jnp.where(page_ids < NB, l * NB + page_ids, L * NB)
    h = jnp.arange(Hkv)[None, :]
    tgt_k = ((page_g[:, None] * 2 + 0) * Hkv + h).reshape(-1)
    tgt_v = ((page_g[:, None] * 2 + 1) * Hkv + h).reshape(-1)
    return jnp.concatenate([tgt_k, tgt_v])


def _kv_page_write_pages(kvp, k, v, l, page_ids, page_rows, page_fill,
                         NB, bs, L, Hkv):
    """Page-granular pool update for prefill-from-zero passes.

    Each plan entry (RaggedBatch.page_ids/rows/fill) covers one page written
    by one contiguous run of chunk rows, so the update is a gather of whole
    pages followed by a scatter of [bs, D] windows over ~CT/bs indices —
    TPU scatters cost per index, and this replaces the CT*Hkv single-row
    scatter (measured 57 ms -> ~6 ms per 32x128-token wave, v5e-1). Rows past
    ``fill`` are zero-filled; they are never read (all readers bound k_pos by
    ctx_len) so overwriting a freed page's stale tail is safe."""
    PW = page_ids.shape[0]
    D = k.shape[-1]
    kg, vg = _page_plan_gather(k, v, page_rows, page_fill, bs)
    kv3 = kvp.reshape(L * NB * 2 * Hkv, bs, D)
    tgt = _page_plan_tgt(page_ids, l, NB, L, Hkv)
    new = jnp.concatenate([kg.reshape(PW * Hkv, bs, D),
                           vg.reshape(PW * Hkv, bs, D)])
    kv3 = kv3.at[tgt].set(new.astype(kvp.dtype), mode="drop")
    return kv3.reshape(-1, D)


def _scale_page_tiles(ksc, vsc, Hkv, bs):
    """Per-page K/V scales [PW, Hkv, bs] x2 -> at-rest tiles [PW, R8, 128]
    (flat order kv*Hkv*bs + h*bs + t, zero-padded to the tile)."""
    PW = ksc.shape[0]
    r8 = _scale_tile_rows(Hkv, bs)
    flat = jnp.concatenate([ksc.reshape(PW, Hkv * bs),
                            vsc.reshape(PW, Hkv * bs)], axis=1)
    pad = r8 * 128 - 2 * Hkv * bs
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    return flat.reshape(PW, r8, 128)


def _kv_page_write_pages_quant(kvp, sc, k, v, l, page_ids, page_rows,
                               page_fill, NB, bs, L, Hkv):
    """int8 variant of :func:`_kv_page_write_pages`: the gathered page
    windows quantize per token-head row; the tiled scale pool
    ([L*NB, R8, 128] view) gets one whole-tile scatter per page."""
    PW = page_ids.shape[0]
    D = k.shape[-1]
    kg, vg = _page_plan_gather(k, v, page_rows, page_fill, bs)
    kgq, kgs = kv_quantize_rows(kg)                                # [PW,Hkv,bs,D]
    vgq, vgs = kv_quantize_rows(vg)
    kv3 = kvp.reshape(L * NB * 2 * Hkv, bs, D)
    tgt = _page_plan_tgt(page_ids, l, NB, L, Hkv)
    new = jnp.concatenate([kgq.reshape(PW * Hkv, bs, D),
                           vgq.reshape(PW * Hkv, bs, D)])
    kv3 = kv3.at[tgt].set(new, mode="drop")
    page_g = jnp.where(page_ids < NB, l * NB + page_ids, L * NB)
    sc = sc.at[page_g].set(_scale_page_tiles(kgs, vgs, Hkv, bs),
                           mode="drop")
    return kv3.reshape(-1, D), sc


def _layer_dest(dest, l, NB, bs, L):
    """Per-layer global token index: padding sentinels (>= NB*bs) must stay
    out of range GLOBALLY — a naive l*NB*bs + sentinel would land inside the
    next layer's pages."""
    return jnp.where(dest >= NB * bs, L * NB * bs, l * NB * bs + dest)


# keys each jitted pass actually reads (engine ships only these; the two
# passes are separate jit programs and the other path's descriptors would be
# dead upload weight)
PAGED_PASS_KEYS = (
    "chunk_tokens", "chunk_positions", "chunk_ntok", "chunk_block_tables",
    "chunk_q0", "chunk_ctx_lens", "decode_tokens", "decode_positions",
    "decode_block_tables", "decode_ctx_lens", "kv_dest")
PREFILL_PASS_KEYS = (
    "chunk_tokens", "chunk_positions", "chunk_ntok", "decode_tokens",
    "row_seg", "page_ids", "page_rows", "page_fill")
#: and, for a model with state-space layers, each pass's rows' state slots
STATE_PASS_KEYS = ("chunk_state_slot", "chunk_state_mode",
                   "decode_state_slot")


def _mamba_body(rs: RaggedModelSpec, positions, rows: _StateRows,
                experts=None, l0=0):
    """The scan body of a run of layers that keep a state (Mamba, Gated
    DeltaNet or power retention: ``rs.mamba["kind"]``), for every serving
    program:
    the carry is ``(x, *the program's KV carry, (ssm, conv))``; the KV part
    passes through untouched and ``l`` is the layer's rank among the Mamba
    layers (:func:`_pool_bases`), ``l - l0`` its place in the run's expert
    stacks where its FFN routes experts (``MambaKind(moe=True)``)."""
    mixer = {"gdn": _gdn_mixer,
             # the one mixer of a state that rotates: positions reach it
             "pr": functools.partial(_pr_mixer, positions=positions),
             }.get(rs.mamba.get("kind"), _mamba_mixer)

    def layer_fn(carry, scanned):
        x, *cache, st = carry
        w, l = scanned[:2]
        moe = {} if rs.moe is None else dict(experts=experts, l=l - l0)
        x, st = _transformer_layer(
            rs, w, x, positions,
            lambda u: mixer(rs, w, u, st, l, rows), **moe)
        return (x, *cache, st), None

    return layer_fn


def build_ragged_forward(spec: RaggedModelSpec,
                         mesh=None,
                         tp: int = 1,
                         n_splits: int = 1) -> Callable:
    """Returns ``fwd(weights, kv_pages, batch) ->
    (chunk_logits [NC, V], decode_logits [S, V], new_kv)`` where
    ``chunk_logits[j]`` holds the logits after slot j's last token
    (``decode_logits`` is ``[0, V]`` for a model that generates by blocks,
    whose passes hold no decode row) — and,
    where the model holds a share of its experts that gives the pass a bound
    (:func:`pass_held_rows_bound`), a fourth result: the int32 count of the
    turns its MoE layers took past their first (:func:`_stream_turns`).

    kv_pages: [L, NB, 2, Hkv, bs, D] combined head-major pages (see
    ragged/kv_cache.py), or an (int8 values, f32 scales) tuple for the
    kv_quant tier. ``batch`` is RaggedBatch.device_arrays().
    When ``tp > 1`` the paged attention kernels run under shard_map on the
    'tensor' axis (heads sharded); everything else partitions via XLA SPMD.
    """
    if spec.mla is not None:
        from deepspeed_tpu.inference.v2.ragged_mla import build_paged_pass
        return build_paged_pass(spec)
    H, Hkv, D = spec.num_heads, spec.num_kv_heads, spec.head_dim
    hid = spec.hidden_size
    dtype = spec.dtype

    def fwd(weights, kv_pages, b):
        NC = b["chunk_ntok"].shape[0]
        CT = b["chunk_tokens"].shape[0]
        Cs = CT // NC
        S = b["decode_tokens"].shape[0]
        # a chunk slot's rows are ONE run from chunk_q0 through its table;
        # the S decode rows are a row a sequence and stay a row scatter
        rw = _run_write_plan(kv_pages, b["chunk_block_tables"], b["chunk_q0"],
                             b["chunk_ntok"], Cs, tp)
        kv_pages, st0 = _state_unpack(kv_pages)
        kv_pages, kv_sc = _kv_unpack(kv_pages)
        kvq = kv_sc is not None
        rows = None if st0 is None else _StateRows(
            b["chunk_state_slot"], b["chunk_state_mode"], b["chunk_ntok"],
            b["decode_state_slot"])
        L, NB, bs = kv_pages.shape[0], kv_pages.shape[1], kv_pages.shape[4]
        kvp0 = kv_pages.reshape(L * NB * 2 * Hkv * bs, D)  # flat (bitcast);
        r8 = _scale_tile_rows(Hkv, bs) if kvq else 0
        sc0 = kv_sc.reshape(L * NB * r8 * 128) if kvq else None
        tokens = jnp.concatenate([b["chunk_tokens"], b["decode_tokens"]])
        positions = jnp.concatenate([b["chunk_positions"], b["decode_positions"]])

        x = _router_stream(
            spec, _embed_in(spec, weights, tokens, positions), weights,
            lambda: _pass_rows_live(b, Cs, True))

        def make_body(rs, experts, l0):
            if rs.mamba is not None:
                return _mamba_body(rs, positions, rows, experts, l0)
            ak = AttentionKernelSpec(rs, mesh=mesh, tp=tp,
                                     n_splits=_kind_splits(spec, rs, n_splits))

            def layer_fn(carry, scanned):
                x, kvp, sc, st = carry
                w, l = scanned

                def attend(q, k, v):
                    dest = _layer_dest(b["kv_dest"], l, NB, bs, L)
                    sc_, scales = sc, None
                    with jax.named_scope("kv_write"):
                        if kvq:
                            kvp_, sc_ = _kv_page_write_quant(
                                kvp, sc, k, v, dest, Hkv, bs)
                            scales = sc_.reshape(L * NB, r8, 128)
                        elif rw is None:
                            kvp_ = _kv_page_write(kvp, k, v, dest, Hkv, bs)
                        else:
                            kvp_ = _kv_run_write(kvp, k[:CT], v[:CT], l, rw,
                                                 kv_pages.shape)
                            kvp_ = _kv_page_write(kvp_, k[CT:], v[CT:],
                                                  dest[CT:], Hkv, bs)
                    kv_l = kvp_.reshape(L * NB, 2, Hkv, bs, D)
                    out_c = ak.chunk(q[:CT].reshape(NC, Cs, H, D), kv_l,
                                     b["chunk_block_tables"] + l * NB,
                                     b["chunk_q0"], b["chunk_ctx_lens"],
                                     kv_scales=scales)
                    out_d = ak.decode(q[CT:], kv_l,
                                      b["decode_block_tables"] + l * NB,
                                      b["decode_ctx_lens"], kv_scales=scales)
                    return (jnp.concatenate([out_c.reshape(CT, H, D), out_d],
                                            axis=0), kvp_, sc_)

                x, (kvp, sc, *tail) = _transformer_layer(
                    rs, w, x, positions, attend, experts=experts, l=l - l0,
                    **_tail_args(rs, st, rows, l))
                return (x, kvp, sc, _tail_kept(st, tail)), None

            return layer_fn

        x, kvp, sc, st = _scan_layers(spec, weights["layers"], make_body,
                                      (x, kvp0, sc0, st0))
        new_kv = kvp.reshape(L, NB, 2, Hkv, bs, D)
        if kvq:
            new_kv = (new_kv, sc.reshape(L, NB, r8, 128))
        new_kv = _state_pack(new_kv, st)

        turns = _stream_turns(x)
        x = _norm(_stream_out(x), weights["final_norm"], spec.norm, spec.eps,
                  dtype, spec.norm_plus_one)
        # only NC + S rows are ever read (parity: ragged_ops/logits_gather —
        # the reference also gathers the needed rows before the unembed GEMM)
        last_rows = (jnp.arange(NC) * Cs
                     + jnp.maximum(b["chunk_ntok"] - 1, 0))    # [NC]
        if spec.causal_block > 1:
            # a model that generates by blocks has no decode row in a pass
            # (the block step is its only decode): no logits of the S idle
            # rows — 78 MB of float32 a pass at 128 rows of 151,936, held
            # from the moment a pass is ENQUEUED, and a burst of prompts
            # enqueues a pass every 2,048 tokens (PERF.md, PR 61)
            logits = _unembed(spec, weights, x[last_rows])
            return (logits, logits[:0], new_kv) + turns
        xs = jnp.concatenate([x[last_rows], x[CT:]], axis=0)   # [NC + S, hid]
        logits = _unembed(spec, weights, xs)
        return (logits[:NC], logits[NC:], new_kv) + turns

    return fwd


def build_prefill_forward(spec: RaggedModelSpec,
                          mesh=None,
                          tp: int = 1) -> Callable:
    """Prefill-from-zero fast path: every token a slot can see was computed IN
    THIS PASS, so attention is one packed segment-masked flash kernel over the
    dense in-pass Q/K/V — no paged reads — and the page write happens AFTER
    attention (the pool is then a pure scatter target riding the layer scan,
    never read-then-written around an opaque kernel call).

    Same signature/outputs as :func:`build_ragged_forward` (decode_logits is
    zeros — a pure-prefill pass has no decode rows). The engine routes here
    when ``RaggedBatch.pure_prefill`` (scheduler.py). Measured v5e-1, 0.55B,
    32x128-token prompts: paged-chunk path 13 ms/layer attention vs ~1 ms
    packed — wave throughput 8k -> 30k+ tok/s.
    """
    if spec.mla is not None:
        from deepspeed_tpu.inference.v2.ragged_mla import build_packed_prefill
        return build_packed_prefill(spec)
    H, Hkv, D = spec.num_heads, spec.num_kv_heads, spec.head_dim
    dtype = spec.dtype

    def fwd(weights, kv_pages, b):
        NC = b["chunk_ntok"].shape[0]
        CT = b["chunk_tokens"].shape[0]
        Cs = CT // NC
        S = b["decode_tokens"].shape[0]
        kv_pages, st0 = _state_unpack(kv_pages)
        kv_pages, kv_sc = _kv_unpack(kv_pages)
        kvq = kv_sc is not None
        # a pass from position 0 only: every chunk slot's state starts from
        # zero or from the slot before it, and there are no decode rows
        rows = None if st0 is None else _StateRows(
            b["chunk_state_slot"], b["chunk_state_mode"], b["chunk_ntok"])
        L, NB, bs = kv_pages.shape[0], kv_pages.shape[1], kv_pages.shape[4]
        kvp0 = kv_pages.reshape(L * NB * 2 * Hkv * bs, D)
        r8 = _scale_tile_rows(Hkv, bs) if kvq else 0
        sc0 = kv_sc.reshape(L * NB, r8, 128) if kvq else None
        tokens = b["chunk_tokens"]
        positions = b["chunk_positions"]
        seg = b["row_seg"]

        x = _router_stream(spec, _embed_in(spec, weights, tokens, positions),
                           weights, lambda: _pass_rows_live(b, Cs, False))

        def make_body(rs, experts, l0):
            if rs.mamba is not None:
                return _mamba_body(rs, positions, rows, experts, l0)
            ak = AttentionKernelSpec(rs, mesh=mesh, tp=tp)

            def layer_fn(carry, scanned):
                x, kvp, sc, st = carry
                w, l = scanned

                def attend(q, k, v):
                    # attention reads the PACKED in-flight rows (full
                    # precision); only the page write quantizes — the fast
                    # path's packed-vs-paged variance already makes equality
                    # gates force the paged path, int8 or not
                    # (docs/SERVING.md "Quantized KV")
                    out = ak.packed(q, k, v, seg)
                    if kvq:
                        kvp_, sc_ = _kv_page_write_pages_quant(
                            kvp, sc, k, v, l, b["page_ids"],
                            b["page_rows"], b["page_fill"], NB, bs, L, Hkv)
                    else:
                        kvp_ = _kv_page_write_pages(
                            kvp, k, v, l, b["page_ids"], b["page_rows"],
                            b["page_fill"], NB, bs, L, Hkv)
                        sc_ = sc
                    return out, kvp_, sc_

                x, (kvp, sc, *tail) = _transformer_layer(
                    rs, w, x, positions, attend, experts=experts, l=l - l0,
                    **_tail_args(rs, st, rows, l))
                return (x, kvp, sc, _tail_kept(st, tail)), None

            return layer_fn

        x, kvp, sc, st = _scan_layers(spec, weights["layers"], make_body,
                                      (x, kvp0, sc0, st0))
        new_kv = kvp.reshape(L, NB, 2, Hkv, bs, D)
        if kvq:
            new_kv = (new_kv, sc.reshape(L, NB, r8, 128))
        new_kv = _state_pack(new_kv, st)

        turns = _stream_turns(x)
        x = _norm(_stream_out(x), weights["final_norm"], spec.norm, spec.eps,
                  dtype, spec.norm_plus_one)
        last_rows = (jnp.arange(NC) * Cs
                     + jnp.maximum(b["chunk_ntok"] - 1, 0))    # [NC]
        logits = _unembed(spec, weights, x[last_rows])
        decode_logits = jnp.zeros((S, logits.shape[1]), logits.dtype)
        return (logits, decode_logits, new_kv) + turns

    return fwd


def _sample_logits(logits, key, do_sample: bool, top_k: int, temperature):
    """The ONE greedy/temperature/top-k sampler of the fused decode step
    (both of its forms, and the latent one). A pipelined stream equals the
    per-token ``sample_next``/``put`` loop under greedy decoding because
    every site runs these exact ops — change it here, nowhere else."""
    if not do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    z = logits / jnp.maximum(temperature, 1e-6)
    if top_k > 0:
        kth = jax.lax.top_k(z, top_k)[0][:, -1:]
        z = jnp.where(z < kth, -jnp.inf, z)
    # the pipeline folds the step's index into ``key`` before the dispatch;
    # this fold is the program's own (a sampled stream depends on it)
    return jax.random.categorical(jax.random.fold_in(key, 0), z,
                                  axis=-1).astype(jnp.int32)


def side_buffer_fits(spec: RaggedModelSpec, tp: int, window_ring_ok: bool,
                     lora_targets: Optional[Tuple[str, ...]]) -> bool:
    """Which form the decode step takes: True, the side buffer (the pool
    frozen through the layers, one row write after them); False, each
    layer's kernel writes its rows. The side-buffer kernel needs one device
    and a lane-aligned head (``head_dim % 128``); LoRA operands are wired
    into the in-layer form only; and with a sliding window the pages stay
    frozen while the step's token is written after them, so the scheduler's
    page ring must cover window + the step (``window_ring_ok``: the caller
    has checked ``scheduler.ring_covers(2)``; unchecked, a windowed model
    takes the in-layer write)."""
    return (lora_targets is None and tp == 1 and spec.head_dim % 128 == 0
            and (spec.window is None or window_ring_ok))


def build_decode_step(spec: RaggedModelSpec, mesh=None, tp: int = 1,
                      do_sample: bool = False, top_k: int = 0,
                      window_ring_ok: bool = False,
                      lora_targets: Optional[Tuple[str, ...]] = None,
                      n_splits: int = 1) -> Callable:
    """One fused decode step for the double-buffered serving pipeline:
    consume ``ids`` [S] (this step's tokens, already sampled), write their KV,
    run the forward pass, and sample the NEXT token row — all in ONE device
    program, so the only thing that ever needs to cross back to the host per
    decode step is the [S] int32 token row (4 bytes/sequence instead of the
    [S, V] logits block the per-token loop fetched). The pipeline chains step
    N+1's dispatch on step N's device-resident token row with no host round
    trip in between: one program decodes one token, and how many tokens a
    run decodes is the host loop's count of dispatches.

    Two forms (``side_buffer_fits`` chooses, from the model and the engine's
    layout) run the same one-pass math: the side buffer, whose K/V write is
    one row write after the layers (scope ``kv_flush``: one row per
    sequence, head and layer), and the in-layer write, whose attention
    kernel writes each layer's rows as it goes. Latent pages have the side
    buffer's form in ``ragged_mla.build_decode_step``.

    Returns ``fwd(weights, kv_pages, ids [S], positions [S],
    block_tables [S, MB], ctx [S], key, temperature, *tail) ->
    (next_ids [S] int32, logits [S, V], new_kv)`` where ``ctx`` counts each
    row's tokens INCLUDING this step's and ``logits`` predict ``next_ids``
    (kept for the engine's continuation refs) — and a fourth result, the
    step's count of overflow turns, where the model holds a share of its
    experts that gives the step's rows a bound (:func:`_stream_turns`). ``tail`` is empty but for:
    ``lora_targets`` set, the two REQUIRED LoRA operands ``(lora_pool,
    adapter_pt)`` — each row's grouped adapter delta rides the targeted
    projections; a model with state-space layers, ``(state_slots [S],)``,
    the rows' slots in the state pools (LoRA is refused beside them).
    """
    if spec.mla is not None:
        # latent pages: the side buffer always (its gates are the K/V
        # kernels'; tp > 1 and LoRA are refused at build)
        from deepspeed_tpu.inference.v2 import ragged_mla
        return ragged_mla.build_decode_step(spec, do_sample, top_k)
    if side_buffer_fits(spec, tp, window_ring_ok, lora_targets):
        return _build_decode_sidebuf(spec, do_sample, top_k, n_splits)
    return _build_decode_layer_write(spec, mesh, tp, do_sample, top_k,
                                     lora_targets, n_splits)


def _state_rows(st0, tail) -> Optional[_StateRows]:
    """The decode rows' state slots out of a decode step's ``tail``."""
    if st0 is None:
        assert not tail, "operands after temperature, and nothing takes them"
        return None
    (state_slots,) = tail       # state pools and the rows' slots come together
    return _StateRows(decode_slot=state_slots)


def _build_decode_sidebuf(spec: RaggedModelSpec, do_sample: bool,
                          top_k: int, n_splits: int = 1) -> Callable:
    """The decode step WITHOUT a pool scatter in any layer.

    Writing each layer's K/V into the paged pools as the layer runs is a
    [S*Hkv]-row scatter per layer; TPU scatter serializes per row, and at
    S=256 those writes cost ~2.5 ms/step — more than the dense compute
    (measured v5e-1, 0.55B GQA: dense-only 1.8 ms, dense+scatter 4.3 ms,
    full 7.0 ms). Here the pools stay FROZEN through the layers:

      - each layer's new K/V rows go to a sequence-major side buffer
        [L, S, rows, D] (one contiguous dynamic_update_slice a layer);
      - attention = ONE fused kernel over the frozen prefix pages plus the
        side slab (``paged_decode_attention_sidebuf``): the side rows fold
        into the same online-softmax state, so the kernel reads one
        sequence's slab into VMEM;
      - after the layers ONE kernel writes the side buffers' rows into the
        pools (``paged_kv_row_write``, scope ``kv_flush``): per sequence the
        aligned group of slots its token falls in, for every layer —
        row-granular, so the step pays for one token's rows. The whole-page
        read-modify-write this replaced moved two pages per sequence per
        layer: a quarter of a 32-row Mistral-7B step on a v5e (PERF.md,
        PR 28).

    ``window`` is admitted (the kernel windows both pieces by the query's
    position); on the page ring the write stays correct because it only
    touches the slot holding position ``prefix``, whose page the ring does
    not recycle within the step.
    """
    Hkv, D = spec.num_kv_heads, spec.head_dim
    dtype = spec.dtype
    # the slab's rows a sequence: the step's Hkv, in whole steps' worth up to
    # the 8-sublane tile (MQA's one row stays on this form; the kernels take
    # the slab's capacity in steps, mask what lies past step 0, and the row
    # write writes step 0 only)
    side_rows = math.lcm(Hkv, 8)

    def fwd(weights, kv_pages, ids, positions, block_tables, ctx,
            key, temperature=1.0, *tail):
        kv_pages, st0 = _state_unpack(kv_pages)
        kv_pages, kv_sc = _kv_unpack(kv_pages)
        kvq = kv_sc is not None
        rows = _state_rows(st0, tail)
        S = ids.shape[0]
        L, NB, bs = kv_pages.shape[0], kv_pages.shape[1], kv_pages.shape[4]
        kvp5 = kv_pages.reshape(L * NB, 2, Hkv, bs, D)
        # scales are stored in kernel tile layout AT REST — the view below
        # is a bitcast, so the frozen-pool reads never pay a conversion
        r8 = _scale_tile_rows(Hkv, bs) if kvq else 0
        sc4 = kv_sc.reshape(L * NB, r8, 128) if kvq else None
        # engine contract: ctx counts tokens INCLUDING this step's; the
        # pages hold only the frozen prefix [0, ctx - 1) — this step's
        # token lives in the side buffers
        prefix = jnp.maximum(ctx - 1, 0)
        # side buffers live PRE-FLATTENED as [L, S, rows, D] (row h of the
        # step): with Hkv second-minor, the per-call reshape to kernel rows
        # relayout-copies the WHOLE buffer at head counts whose (Hkv, D)
        # tile pads (measured: 14 ms/step vs 2.9 at MHA-12 — the same
        # padded-sublane trap the kv pool layout avoids, kv_cache.py).
        # int8 pools: the slab holds kv_write_dequant'd POOL values, kept
        # f32 so a bf16 slab round-trip cannot round them away from what
        # every pool read (int8 * f32 scale, in f32) computes
        side_dtype = jnp.float32 if kvq else dtype
        side_k0 = jnp.zeros((L, S, side_rows, D), side_dtype)
        side_v0 = jnp.zeros((L, S, side_rows, D), side_dtype)

        x = _router_stream(spec, _embed_in(spec, weights, ids, positions),
                           weights)

        def make_body(rs, experts, l0):
            if rs.mamba is not None:
                return _mamba_body(rs, positions, rows, experts, l0)
            ak = AttentionKernelSpec(
                rs, mesh=None, tp=1,
                n_splits=_kind_splits(spec, rs, n_splits))

            def layer_fn(carry, scanned):
                # side buffers ride the CARRY with in-place dynamic
                # updates — as scan xs/ys they are repacked (a full
                # side-buffer copy per layer, measured slower than the
                # scatter they replace)
                x, sk_all, sv_all, st = carry
                w, l = scanned

                def attend(q, k, v):
                    if kvq:
                        # int8 pools: the slab holds the rows' POOL
                        # values (quantize-then-dequantize), so the
                        # step's token is attended at the same values
                        # every later pool read — and the spec verify's
                        # write-then-attend — dequantizes; the row write
                        # re-quantizes to the identical int8 bytes
                        # (kv_write_dequant is value-idempotent)
                        k = kv_write_dequant(k)
                        v = kv_write_dequant(v)
                    # the step's rows are the flat span [0, Hkv)
                    sk_new = jax.lax.dynamic_update_slice(
                        sk_all, k[None].astype(sk_all.dtype), (l, 0, 0, 0))
                    sv_new = jax.lax.dynamic_update_slice(
                        sv_all, v[None].astype(sv_all.dtype), (l, 0, 0, 0))
                    # the WHOLE [L, S, rows, D] stack goes to the kernel,
                    # which BlockSpec-indexes layer l — a dynamic_slice
                    # here would materialise the layer's slab per call
                    # (measured ~150 us/layer of pure copy traffic)
                    out = ak.sidebuf(
                        q, kvp5, block_tables + l * NB, prefix,
                        sk_new, sv_new, 0, layer_idx=l,
                        kv_scales=sc4 if kvq else None)
                    return out, sk_new, sv_new

                x, (sk_all, sv_all, *tail) = _transformer_layer(
                    rs, w, x, positions, attend, experts=experts, l=l - l0,
                    **_tail_args(rs, st, rows, l))
                return (x, sk_all, sv_all, _tail_kept(st, tail)), None

            return layer_fn

        x, sk_all, sv_all, st = _scan_layers(
            spec, weights["layers"], make_body, (x, side_k0, side_v0, st0))
        turns = _stream_turns(x)
        x = _norm(_stream_out(x), weights["final_norm"], spec.norm, spec.eps,
                  dtype, spec.norm_plus_one)
        logits = _unembed(spec, weights, x)

        # ---- the side buffers' rows -> the pool ---- #
        # the kernels READ the pool inside the layers; the barrier ties the
        # write's pool operand to their result so XLA orders the in-place
        # write after the reads instead of cloning the (GB-scale) pool
        if num_page_layers(spec):
            kv_pages, kv_sc, _ = jax.lax.optimization_barrier(
                (kv_pages, kv_sc, logits))
            with jax.named_scope("kv_flush"):
                new_kv = paged_kv_row_write(kv_pages, sk_all, sv_all,
                                            block_tables, prefix, 1,
                                            kv_scales=kv_sc)
        else:       # no layer wrote a row: the pool is its scratch page
            new_kv = kv_pages if kv_sc is None else (kv_pages, kv_sc)
        nxt = _sample_logits(logits, key, do_sample, top_k, temperature)
        return (nxt, logits, _state_pack(new_kv, st)) + turns

    return fwd


def _build_decode_layer_write(spec: RaggedModelSpec, mesh, tp: int,
                              do_sample: bool, top_k: int,
                              lora_targets: Optional[Tuple[str, ...]],
                              n_splits: int) -> Callable:
    """The decode step whose attention kernel writes each layer's rows
    (fused attention + page write, ``paged_decode_attention_step``): the
    form of what ``side_buffer_fits`` turns away (TP sharding, a small
    head_dim, a window whose ring was not checked, LoRA operands — docs/
    SERVING.md "Multi-tenant LoRA")."""
    Hkv, D = spec.num_kv_heads, spec.head_dim
    dtype = spec.dtype

    def fwd(weights, kv_pages, ids, positions, block_tables, ctx,
            key, temperature=1.0, *tail):
        kv_pages, st0 = _state_unpack(kv_pages)
        kv_pages, kv_sc = _kv_unpack(kv_pages)
        kvq = kv_sc is not None
        assert not (kvq and tp > 1), "int8 KV pages + TP not wired"
        L, NB, bs = kv_pages.shape[0], kv_pages.shape[1], kv_pages.shape[4]
        r8 = _scale_tile_rows(Hkv, bs) if kvq else 0
        lora_ops = None
        if lora_targets is not None:
            # (LoRA is refused beside state-space layers)
            lora_pool, adapter_pt = tail
            lora_ops = lora_layer_operands(spec, lora_targets, lora_pool,
                                           adapter_pt)
            tail = ()
        rows = _state_rows(st0, tail)

        # kvp flat [L*NB*2*Hkv*bs, D]. The attention + page-write is one
        # fused unit (paged_decode_attention_step): pool aliased through
        # the kernel, new rows scattered in place after — the pool flows
        # through the layer scan with no copies (see the kernel docstring
        # for why a pre-kernel scatter forces XLA to clone the pool).
        x = _router_stream(spec, _embed_in(spec, weights, ids, positions),
                           weights)

        def make_body(rs, experts, l0):
            if rs.mamba is not None:
                return _mamba_body(rs, positions, rows, experts, l0)
            ak = AttentionKernelSpec(
                rs, mesh=mesh, tp=tp,
                n_splits=_kind_splits(spec, rs, n_splits))

            def layer_fn(carry, scanned):
                x, kvp, sc, st = carry
                if lora_ops is not None:
                    w, l, lora_l = scanned
                    lora = _lora_split(spec, lora_targets, lora_l)
                else:
                    w, l = scanned
                    lora = None

                def attend(q, k, v):
                    if kvq:
                        # the current token is attended from registers:
                        # hand the kernel its POOL value (the in-kernel
                        # re-quantization for the page write is
                        # value-idempotent) so this form agrees with the
                        # write-then-attend paths on the attended VALUES
                        k = kv_write_dequant(k)
                        v = kv_write_dequant(v)
                        out, kv5, sc4 = ak.decode_step(
                            q, k, v, kvp.reshape(L * NB, 2, Hkv, bs, D),
                            block_tables + l * NB, ctx,
                            kv_scales=sc.reshape(L * NB, r8, 128))
                        return (out,
                                kv5.reshape(L * NB * 2 * Hkv * bs, D),
                                sc4.reshape(L * NB * r8 * 128))
                    out, kv5 = ak.decode_step(
                        q, k, v, kvp.reshape(L * NB, 2, Hkv, bs, D),
                        block_tables + l * NB, ctx)
                    return (out, kv5.reshape(L * NB * 2 * Hkv * bs, D), sc)

                x, (kvp, sc, *tail) = _transformer_layer(
                    rs, w, x, positions, attend, lora=lora, experts=experts,
                    l=l - l0, **_tail_args(rs, st, rows, l))
                return (x, kvp, sc, _tail_kept(st, tail)), None

            return layer_fn

        kvp0 = kv_pages.reshape(L * NB * 2 * Hkv * bs, D)
        sc0 = kv_sc.reshape(L * NB * r8 * 128) if kvq else None
        x, kvp, sc, st = _scan_layers(
            spec, weights["layers"], make_body, (x, kvp0, sc0, st0),
            extra_xs=() if lora_ops is None else (lora_ops,))
        turns = _stream_turns(x)
        x = _norm(_stream_out(x), weights["final_norm"], spec.norm, spec.eps,
                  dtype, spec.norm_plus_one)
        logits = _unembed(spec, weights, x)
        new_kv = kvp.reshape(L, NB, 2, Hkv, bs, D)
        if kvq:
            new_kv = (new_kv, sc.reshape(L, NB, r8, 128))
        nxt = _sample_logits(logits, key, do_sample, top_k, temperature)
        return (nxt, logits, _state_pack(new_kv, st)) + turns

    return fwd


def build_verify_step(spec: RaggedModelSpec, k: int, mesh=None,
                      tp: int = 1,
                      lora_targets: Optional[Tuple[str, ...]] = None,
                      n_splits: int = 1) -> Callable:
    """Speculative-decode verify step: score ``k`` draft tokens per sequence
    in ONE ragged forward (``inference/v2/spec/``; docs/SERVING.md
    "Speculative decoding").

    Each sequence contributes K+1 = ``k + 1`` rows — its committed current
    token (device-resident, sampled by the previous step) followed by the
    host-proposed draft. Every layer scatters all K+1 rows' K/V into the
    paged pool (the same flat-scatter the ragged pass uses), then attends
    with the batched chunk kernel: one slot per sequence, causal by absolute
    position, so row j sees exactly the frozen prefix plus in-pass rows
    0..j. That per-row visible set — and the kernel's page-ordered online
    softmax — is identical to what ``build_decode_step`` computes one token
    at a time, so for any row whose consumed prefix matches the greedy
    stream the logits are BIT-EQUAL to sequential decode (the exactness
    induction the byte-identical stream rests on; pinned by
    tests/unit/test_spec_decode.py).

    The greedy accept mask is computed ON DEVICE: draft token j+1 is
    accepted iff it equals ``argmax(logits[:, j])`` and every earlier draft
    was accepted (``n_draft`` bounds per-row proposals — rows past their
    proposal count never accept, so per-sequence adaptive k rides a traced
    operand instead of a recompile). The per-step host transfer is ONE
    int32 ``[2, S]`` row — accept counts and bonus tokens — mirroring the
    decode pipeline's one-row discipline; the host reconstructs the emitted
    tokens from the draft it proposed.

    Rejected rows' K/V stays in the pool as stale bytes past the advanced
    context — never read (every reader is ctx-bounded) and overwritten by
    the next write at those positions; block-granular reclamation of
    reserved-but-unused pages is the scheduler's ``rollback_reserved``.

    int8 pools compose: the per-layer write is the quantize-on-write
    append (``_kv_page_write_quant``) and the chunk kernel dequantizes
    in-flight, so every in-pass token is attended at its POOL value —
    the same value sequential decode attends (the ``kv_write_dequant``
    discipline; docs/SERVING.md "Quantized KV").

    Returns ``fwd(weights, kv_pages, ids [S], draft [S, k], n_draft [S],
    positions [S], block_tables [S, MB], ctx [S]) -> (accept_row [2, S]
    int32, next_ids [S] int32, final_logits [S, V], new_kv)`` where
    ``accept_row[0]`` counts accepted draft tokens (row i emits
    ``accept_row[0, i] + 1`` tokens: the accepted prefix plus
    ``accept_row[1] = next_ids``, the greedy bonus/correction token) and
    ``final_logits`` predict ``next_ids``'s successor source row (the
    engine's continuation refs).
    """
    if num_state_layers(spec):
        raise NotImplementedError(STATE_SNAPSHOT_MSG.format(
            what="the speculative verify step (rejected drafts have already "
            "advanced the state; rolling back needs the state before them)"))
    if spec.mla is not None:
        from deepspeed_tpu.inference.v2.ragged_mla import build_verify
        return build_verify(spec, k)
    H, Hkv, D = spec.num_heads, spec.num_kv_heads, spec.head_dim
    dtype = spec.dtype
    K1 = k + 1

    def fwd(weights, kv_pages, ids, draft, n_draft, positions0,
            block_tables, ctx0, *lora_args):
        kv_pages, kv_sc = _kv_unpack(kv_pages)
        kvq = kv_sc is not None
        assert not (kvq and tp > 1), "int8 KV pages + TP not wired"
        S = ids.shape[0]
        if lora_targets is not None:
            # each sequence's K+1 token rows share its adapter: repeat the
            # per-sequence gather to token rows so the verify batch runs the
            # SAME grouped delta sequential decode runs row-for-row (the
            # byte-equality induction extends to LoRA streams unchanged)
            lora_pool, adapter_pt = lora_args
            lora_ops = lora_layer_operands(spec, lora_targets, lora_pool,
                                           adapter_pt, repeat=K1)
        else:
            assert not lora_args, "lora operands on a non-LoRA program"
            lora_ops = None
        L, NB, bs = kv_pages.shape[0], kv_pages.shape[1], kv_pages.shape[4]
        kvp0 = kv_pages.reshape(L * NB * 2 * Hkv * bs, D)
        r8 = _scale_tile_rows(Hkv, bs) if kvq else 0
        sc0 = kv_sc.reshape(L * NB * r8 * 128) if kvq else None
        tokens = jnp.concatenate([ids[:, None], draft], axis=1)    # [S, K1]
        positions = positions0[:, None] + jnp.arange(K1, dtype=jnp.int32)[None]
        pos_flat = positions.reshape(-1)
        # the run's reservation covers positions0 + K1
        dest = _rows_dest(block_tables, positions, bs)

        x = _embed_in(spec, weights, tokens.reshape(-1), pos_flat)

        def make_body(rs, experts, l0):
            ak = AttentionKernelSpec(rs, mesh=mesh, tp=tp,
                                     n_splits=_kind_splits(spec, rs, n_splits))

            def layer_fn(carry, scanned):
                x, kvp, sc = carry
                if lora_ops is not None:
                    w, l, lora_l = scanned
                    lora = _lora_split(spec, lora_targets, lora_l)
                else:
                    w, l = scanned
                    lora = None

                def attend(q, k_, v):
                    # write-then-attend (the ragged pass's discipline): all
                    # K+1 rows' K/V scatter into the pool — quantize-on-write
                    # for int8 pools, the same fused append the decode step
                    # runs — then the chunk kernel reads pages causally
                    # (dequantizing in-flight), row j's own token included:
                    # every in-pass token is attended at its POOL value,
                    # exactly what sequential decode attends
                    # (docs/SERVING.md "Quantized KV")
                    dl = _layer_dest(dest, l, NB, bs, L)
                    if kvq:
                        kvp_, sc_ = _kv_page_write_quant(kvp, sc, k_, v, dl,
                                                         Hkv, bs)
                        scales = sc_.reshape(L * NB, r8, 128)
                    else:
                        kvp_ = _kv_page_write(kvp, k_, v, dl, Hkv, bs)
                        sc_, scales = sc, None
                    kv_l = kvp_.reshape(L * NB, 2, Hkv, bs, D)
                    out = ak.chunk(q.reshape(S, K1, H, D), kv_l,
                                   block_tables + l * NB, positions0,
                                   ctx0 + (K1 - 1), kv_scales=scales)
                    return out.reshape(S * K1, H, D), kvp_, sc_

                x, (kvp, sc) = _transformer_layer(
                    rs, w, x, pos_flat, attend, lora=lora, experts=experts,
                    l=l - l0)
                return (x, kvp, sc), None

            return layer_fn

        x, kvp, sc = _scan_layers(
            spec, weights["layers"], make_body, (x, kvp0, sc0),
            extra_xs=() if lora_ops is None else (lora_ops,))
        new_kv = kvp.reshape(L, NB, 2, Hkv, bs, D)
        if kvq:
            new_kv = (new_kv, sc.reshape(L, NB, r8, 128))

        x = _norm(x, weights["final_norm"], spec.norm, spec.eps, dtype,
                  spec.norm_plus_one)
        logits = _unembed(spec, weights, x).reshape(S, K1, -1)
        return _greedy_accept(logits, draft, n_draft) + (new_kv,)

    return fwd


def _rows_dest(block_tables, positions, bs):
    """Flat pool write destinations (page * bs + slot, before the layer's
    offset: :func:`_layer_dest`) of the rows at ``positions`` ``[S, R]`` of
    sequences with ``block_tables`` ``[S, MB]`` — the verify step's and the
    block step's. The caller's reservation covers the positions, so the
    logical page index is always inside the table (pad rows' all-scratch
    tables clamp to the scratch page)."""
    MB = block_tables.shape[1]
    page = jnp.take_along_axis(block_tables,
                               jnp.minimum(positions // bs, MB - 1),
                               axis=1)                              # [S, R]
    return (page * bs + positions % bs).reshape(-1)


def _greedy_accept(logits, draft, n_draft):
    """The verify step's accept rule on its logits ``[S, k + 1, V]``:
    ``(accept_row [2, S], next_ids [S], final_logits [S, V])``. The SAME
    argmax ``_sample_logits`` greedy runs, so an accepted token is exactly
    the token sequential decode would emit."""
    S, k = draft.shape
    pred = jnp.argmax(logits, axis=-1).astype(jnp.int32)           # [S, K1]
    match = (pred[:, :k] == draft) if k else jnp.zeros((S, 0), bool)
    match = match & (jnp.arange(k, dtype=jnp.int32)[None]
                     < n_draft[:, None])
    accept = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)
    next_ids = jnp.take_along_axis(pred, accept[:, None], axis=1)[:, 0]
    final_logits = jnp.take_along_axis(
        logits, accept[:, None, None], axis=1)[:, 0]               # [S, V]
    accept_row = jnp.stack([accept, next_ids]).astype(jnp.int32)
    return accept_row, next_ids, final_logits


def build_block_step(spec: RaggedModelSpec, mesh=None, tp: int = 1) -> Callable:
    """The block step of a model that generates by diffusion over blocks
    (``spec.causal_block`` B > 1, ``spec.mask_token_id`` m; ``inference/v2/
    blocks/``; docs/SERVING.md "Block-diffusion generation"): ONE pass over
    the current block of every live sequence, whatever phase each is in.

    Each sequence contributes its block's B rows at positions ``ctx0 .. ctx0
    + B - 1`` — tokens already chosen and mask tokens side by side. Every
    layer writes the B rows' K/V into the pages there (a block is one run:
    ``_kv_run_write``; write-then-attend) and attends with the batched chunk
    kernel under the BLOCK rule (``AttentionKernelSpec.chunk`` binds
    ``causal_block``): every row of the block sees the whole cached context
    and all B rows of its own block. The logits at a row score the token AT
    that row (no shift). After the head, ON THE DEVICE: at each still-masked
    position ``x0 = argmax`` (the mask token itself is never chosen) and
    ``conf = max softmax`` in float32; of a row's masked positions those
    with ``conf > threshold`` take their ``x0`` if they are at least
    ``n_take`` of them, else the ``n_take`` of highest ``conf`` (ties to the
    lower position) — the static schedule hands ``threshold = inf``, the
    dynamic rule a finite one, the same program. ``n_take = 0`` takes
    nothing: a pad row, or a row at its COMMIT — its block holds no mask, so
    this pass's write IS the final K/V of the block (a denoise pass's rows
    saw masks beside them and are overwritten), and the host advances it by
    B and hands it the next block.

    The block a row runs on is ``block_ids`` (device-resident: the pass
    before's ``new_ids``) or, where ``fresh`` is set, ``fresh_ids`` (the
    host's: a block of masks after a commit, with the left-over prompt tokens
    in front for a first block).

    Returns ``fwd(weights, kv_pages, block_ids [S, B], fresh_ids [S, B],
    fresh [S], n_take [S], block_tables [S, MB], ctx0 [S], threshold f32)
    -> (new_ids [S, B] int32, masks_left [S] int32, logits [S * B, V] f32
    (row ``i * B + r`` is block row ``r`` of sequence ``i``), new_kv)``; the
    logits stay on the device for a check to fetch."""
    B, mask_id = spec.causal_block, spec.mask_token_id
    if B <= 1 or mask_id is None:
        raise ValueError("the block step is for a model that generates by "
                         "diffusion over blocks (causal_block > 1 and a "
                         "mask_token_id)")
    if num_state_layers(spec) or spec.mla is not None or spec.layer_kinds:
        raise NotImplementedError(BLOCK_DIFFUSION_MSG.format(
            what="a block step beside a state, latent pages or layers of "
            "several kinds"))
    H, Hkv, D = spec.num_heads, spec.num_kv_heads, spec.head_dim
    dtype = spec.dtype

    def fwd(weights, kv_pages, block_ids, fresh_ids, fresh, n_take,
            block_tables, ctx0, threshold):
        kv_pages, kv_sc = _kv_unpack(kv_pages)
        assert kv_sc is None, "int8 KV pages + the block step not wired"
        S = block_ids.shape[0]
        L, NB, bs = kv_pages.shape[0], kv_pages.shape[1], kv_pages.shape[4]
        kvp0 = kv_pages.reshape(L * NB * 2 * Hkv * bs, D)
        ids = jnp.where(fresh[:, None] > 0, fresh_ids, block_ids)   # [S, B]
        positions = ctx0[:, None] + jnp.arange(B, dtype=jnp.int32)[None]
        pos_flat = positions.reshape(-1)
        # the run's reservation covers ctx0 + B. A block's rows are ONE run
        # of B from ctx0, a multiple of B (the block rule's own premise)
        rw = _run_write_plan(kv_pages, block_tables, ctx0,
                             jnp.full_like(ctx0, B), B, tp, aligned=True)
        dest = _rows_dest(block_tables, positions, bs) if rw is None else None

        x = _embed_in(spec, weights, ids.reshape(-1), pos_flat)

        def make_body(rs, experts, l0):
            ak = AttentionKernelSpec(rs, mesh=mesh, tp=tp)

            def layer_fn(carry, scanned):
                x, kvp = carry
                w, l = scanned

                def attend(q, k_, v):
                    with jax.named_scope("kv_write"):
                        if rw is None:
                            kvp_ = _kv_page_write(
                                kvp, k_, v, _layer_dest(dest, l, NB, bs, L),
                                Hkv, bs)
                        else:
                            kvp_ = _kv_run_write(kvp, k_, v, l, rw,
                                                 kv_pages.shape)
                    out = ak.chunk(q.reshape(S, B, H, D),
                                   kvp_.reshape(L * NB, 2, Hkv, bs, D),
                                   block_tables + l * NB, ctx0, ctx0 + B)
                    return out.reshape(S * B, H, D), kvp_

                x, (kvp,) = _transformer_layer(rs, w, x, pos_flat, attend,
                                               experts=experts, l=l - l0)
                return (x, kvp), None

            return layer_fn

        with jax.named_scope("block_step"):
            x, kvp = _scan_layers(spec, weights["layers"], make_body,
                                  (x, kvp0))
            x = _norm(x, weights["final_norm"], spec.norm, spec.eps, dtype,
                      spec.norm_plus_one)
            # (the logits stay [S * B, V]: as [S, B, V] the chip's tiles of
            # 8 x 128 pad B = 4 to 8 and the reshape is a copy of 311 MB at
            # 512 rows of 151,936 — 1.6 ms of a 27 ms pass; PERF.md, PR 61)
            logits = _unembed(spec, weights, x)
            with jax.named_scope("denoise"):
                new_ids, left = denoise_choice(logits, ids, n_take, mask_id,
                                               threshold)
        return new_ids, left, logits, kvp.reshape(L, NB, 2, Hkv, bs, D)

    return fwd


def denoise_choice(logits, ids, n_take, mask_id: int, threshold):
    """One denoise pass's choice (:func:`build_block_step`): ``logits`` ``[S
    * B, V]`` float32 of the blocks ``ids`` ``[S, B]``, ``n_take`` ``[S]`` ->
    ``(new_ids [S, B], masks_left [S])``. B is small: the ``n_take`` best of
    a row's masked positions are those fewer than ``n_take`` others come
    before, by confidence and then by position — a few compares, no sort."""
    S, B = ids.shape
    V = logits.shape[-1]
    lg = jnp.where(jnp.arange(V, dtype=jnp.int32) == mask_id, -jnp.inf,
                   logits)
    x0 = jnp.argmax(lg, axis=-1).astype(jnp.int32).reshape(S, B)
    top = jnp.max(lg, axis=-1)
    conf = (1.0 / jnp.sum(jnp.exp(lg - top[..., None]), axis=-1)
            ).reshape(S, B)                                         # (0, 1]
    masked = ids == mask_id
    conf = jnp.where(masked, conf, -1.0)
    at = jnp.arange(B, dtype=jnp.int32)
    ci, cj = conf[:, :, None], conf[:, None, :]
    before = (cj > ci) | ((cj == ci) & (at[None, None, :] < at[None, :, None]))
    rank = jnp.sum(before, axis=-1).astype(jnp.int32)               # [S, B]
    over = masked & (conf > threshold)
    enough = jnp.sum(over, axis=-1) >= n_take
    take = masked & jnp.where(enough[:, None], over,
                              rank < n_take[:, None])
    new_ids = jnp.where(take, x0, ids)
    left = jnp.sum(masked & ~take, axis=-1).astype(jnp.int32)
    return new_ids, left
