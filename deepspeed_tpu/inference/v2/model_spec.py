"""What a served model is made of, as data: the kinds a layer can be, the
:class:`RaggedModelSpec` that names a model's widths and kinds, and the pure
functions of it that say how the layers are scanned and which pool each
addresses.

The leaf of ``inference/v2``: the adapters (``adapters/``) write a spec, the
program builders (``ragged_model.py``, ``ragged_mla.py``) trace from one, the
engine sizes its pools from one. Nothing here traces, and nothing here
imports another module of ``inference/v2``. A new kind of per-sequence state
is a kind here and a mixer in ``ragged_model.py``.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax.numpy as jnp

from deepspeed_tpu.ops.pallas.mla_attention import latent_row_width


class LayerKind(NamedTuple):
    """What may differ from one layer of a model to the next."""
    window: Optional[int]   # sliding-window span in tokens; None = full
    rope: bool              # rotates q/k by position (else no positions)
    moe: bool               # routed experts (else the dense MLP)
    mamba = False           # an attention layer (else: Mamba-/Delta-/PowerKind)
    block = None            # the pair: mixer, then FFN (else: BlockKind)
    tail = False            # pages alone (else: CcaKind)

    def describe(self) -> str:
        attn = "full" if self.window is None else f"window {self.window}"
        return (f"{attn}, {'rotary' if self.rope else 'no positions'}, "
                f"{'MoE' if self.moe else 'dense'} FFN")


class MambaKind(NamedTuple):
    """The kind of a layer whose mixer is a Mamba state-space block, not
    attention: it holds no pages and has neither window nor positions, and
    keeps a fixed-size state per sequence (ragged/state_pool.py). Which
    recurrence (Mamba-1, Mamba-2) is the model's, not the layer's
    (``RaggedModelSpec.mamba``). A kind of its own beside
    :class:`LayerKind`, which stays the three values an attention layer is
    told by."""
    moe: bool = False       # routed experts (else the dense MLP)
    window = None
    rope = False
    mamba = True
    block = None
    tail = False

    def describe(self) -> str:
        return ("Mamba state-space mixer (no pages), "
                f"{'MoE' if self.moe else 'dense'} FFN")


class DeltaKind(NamedTuple):
    """The kind of a layer whose mixer is a Gated DeltaNet block (qwen3_next):
    linear attention whose state a head is a matrix CORRECTED by a delta
    rule, where a Mamba state decays and takes a rank-one term added. To the
    pools it is a Mamba layer — no pages, no window, no positions, one slot of
    the state pool a sequence (``mamba`` is true, and ``RaggedModelSpec.mamba``
    holds its widths under ``"kind": "gdn"``) — and to the layer loop a kind
    of its own, with a mixer (:func:`_gdn_mixer`) and scopes (``gdn/..``) of
    its own."""
    moe: bool = False       # routed experts (else the dense MLP)
    window = None
    rope = False
    mamba = True
    block = None
    tail = False

    def describe(self) -> str:
        return ("Gated DeltaNet delta-rule mixer (no pages), "
                f"{'MoE' if self.moe else 'dense'} FFN")


class PowerKind(NamedTuple):
    """The kind of a layer whose mixer is power retention (brumby;
    ``ops/pallas/power_retention.py`` states it): linear attention whose
    state a KV head is the sum of values times the key's symmetric SQUARE,
    decayed by a gate a token, read through the query's square and divided
    by a normaliser carried beside it. To the pools it is a Mamba layer — no
    pages, no window, one slot of the state pool a sequence (``mamba`` is
    true, and ``RaggedModelSpec.mamba`` holds its widths under ``"kind":
    "pr"``) — but it ROTATES: q and k are normed and rotated by position
    before they reach the state, so positions reach this layer as they reach
    an attention layer; and it keeps no convolution tail. A mixer
    (:func:`_pr_mixer`) and scopes (``pr/..``) of its own."""
    moe: bool = False       # routed experts (else the dense MLP)
    window = None
    rope = True
    mamba = True
    block = None
    tail = False

    def describe(self) -> str:
        return ("power-retention mixer (rotary; no pages), "
                f"{'MoE' if self.moe else 'dense'} FFN")


#: ``RaggedModelSpec.mamba["kind"]`` -> the kind of a layer that keeps such a
#: state (absent: Mamba-1)
_STATE_KINDS = {"gdn": DeltaKind, "pr": PowerKind}


class CcaKind(NamedTuple):
    """The kind of a layer whose attention is compressed convolutional
    attention (zaya; :func:`_cca_project`): q and k are mixed along the
    sequence by two small causal convolutions before they attend, and one
    value head is the previous token's. Such a layer addresses BOTH pools: it
    writes K and V into pages as any attention layer does (full, rotary), and
    keeps a tail of the convolutions' last inputs in a slot of the state
    pool (``tail``), with no recurrent state beside it
    (``RaggedModelSpec.cca`` holds its widths)."""
    moe: bool = True        # routed experts (else the dense MLP)
    window = None
    rope = True
    mamba = False
    block = None
    tail = True

    def describe(self) -> str:
        return ("compressed convolutional attention (full, rotary; pages "
                "and a convolution tail), "
                f"{'MoE' if self.moe else 'dense'} FFN")


class BlockKind(NamedTuple):
    """The kind of a layer that is ONE block, ``x + block(norm(x))``, where
    the two kinds above are a mixer followed by an FFN (nemotron_h: a Mamba
    mixer, OR attention, OR routed experts, and nothing else in the layer).
    A layer of experts or of a dense MLP alone addresses neither pool: it
    holds no pages and no state."""
    what: str                       # "mamba" | "attention" | "moe" | "mlp"
    window: Optional[int] = None    # of an attention block
    rope: bool = False
    tail = False

    @property
    def mamba(self) -> bool:
        return self.what == "mamba"

    @property
    def moe(self) -> bool:
        return self.what == "moe"

    @property
    def block(self) -> str:         # which half of the pair the layer is
        return "mixer" if self.what in ("mamba", "attention") else "ffn"

    def describe(self) -> str:
        if self.what == "mamba":
            return "Mamba state-space mixer alone (no pages)"
        if self.what == "attention":
            attn = "full" if self.window is None else f"window {self.window}"
            return (f"attention alone ({attn}, "
                    f"{'rotary' if self.rope else 'no positions'})")
        return ("routed experts" if self.moe else "dense MLP") \
            + " alone (no pages, no state)"


def _holds(kind) -> Optional[str]:
    """The pool a layer of ``kind`` addresses: ``"state"`` (a mixer that
    keeps a state: Mamba, Gated DeltaNet, power retention — whether or not it
    rotates by position), ``"pages"`` (attention), ``"both"`` (attention that
    keeps a convolution tail: :class:`CcaKind`) or None (an FFN alone)."""
    if kind.mamba:
        return "state"
    if kind.tail:
        return "both"
    return None if kind.block == "ffn" else "pages"


@dataclass
class RaggedModelSpec:
    family: str
    num_layers: int
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    vocab_size: int
    norm: str = "rms"              # "rms" | "ln"
    # gated: "swiglu" (silu gate) | "geglu" (tanh-gelu gate, Gemma)
    # plain: "gelu" (tanh) | "gelu_exact" (erf) | "silu" | "relu" | "relu2"
    activation: str = "swiglu"
    rope_theta: Optional[float] = 10000.0   # None -> no rotary
    rotary_dim: Optional[int] = None        # partial rotary (phi); None = full head
    learned_pos: bool = False      # gpt2/opt learned position embeddings
    pos_offset: int = 0            # opt: positions are offset by 2 in the table
    parallel_block: bool = False   # falcon/phi: attn + mlp both from the same norm
    parallel_dual_norm: bool = False  # gpt_neox: parallel, but MLP from ln2(x)
    tied_lm_head: bool = False     # gpt2: logits = x @ embed.T
    head_bias: bool = False        # phi/gpt-j: bias added to the logits
    embed_scale_by_sqrt_dim: bool = False  # gemma: x *= sqrt(hidden) after embed
    norm_plus_one: bool = False    # gemma: RMSNorm scales by (1 + weight)
    eps: float = 1e-5
    # {"num_experts": E, "top_k": k}: top-k of the router logits, softmax
    # over the chosen (Mixtral). With "score_func": "sigmoid" the scores are
    # sigmoid(logits), chosen with the layer's "expert_bias" added, weighed
    # without it, over their sum if "route_norm", times "route_scale" (afmoe,
    # joyai). "router": "mlp" (zaya) — an MLP of width "router_hidden" on a
    # state that every layer's router adds to and hands to the next
    # (:func:`moe_route_mlp`), top-1 by its softmax with a stored bias, the
    # weight not renormalised; with "skip" it scores one choice more than
    # there are experts, and a token that takes it passes no expert.
    # "held": (first, count) — the expert stacks hold only experts
    # first..first+count-1 of the E the router scores (one chip's share of an
    # expert-parallel deployment); absent: all E. "act": the plain activation
    # of experts that are two matrices (no gate stack), as ``activation``
    # names them; absent: "gelu"
    moe: Optional[Dict[str, Any]] = None
    # multi-head latent attention: {"q_lora_rank", "kv_lora_rank",
    # "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"}. The pages then
    # hold one latent row a token a layer (no head axis, no K/V pair:
    # ragged/kv_cache.py) and the programs are ragged_mla.py's. With "index":
    # {"heads", "head_dim", "topk", "rope_dim", "eps"} every layer selects
    # the topk cached tokens a query attends to (``adapt_glm_dsa``) and a
    # second pool holds one index key a token a layer
    mla: Optional[Dict[str, Any]] = None
    # mistral/qwen2 sliding-window span (tokens); None = full attention.
    # Reference parity: inference/v2/model_implementations/mistral.
    window: Optional[int] = None
    # one kind per layer, for a model whose layers differ in attention
    # (window or full, rotary or none) or FFN (dense or MoE). None: every
    # layer is of the one kind the scalar fields give. Where kinds differ,
    # ``window`` is None (no page ring: every layer holds whole-context
    # pages), ``rope_theta`` and ``moe`` describe the layers that have them,
    # and ``weights["layers"]`` is a tuple with one entry per unit the layer
    # loop scans (:func:`layer_units`): a run's stacked tree, or for a unit of
    # several kinds that repeats a tuple of stacked trees, one a kind
    layer_kinds: Optional[Tuple[Any, ...]] = None   # Layer-/Mamba-/Delta-/Power-/BlockKind
    # on a run's spec (:func:`layer_runs`) of layers that are one block
    # (:class:`BlockKind`): "mixer" (no FFN follows) or "ffn" (no mixer
    # before it). None: the pair every other layer is
    block: Optional[str] = None
    # widths of the Mamba mixer of a model that has such layers
    # (``layer_kinds`` says which); on a run's spec (:func:`layer_runs`) it is
    # set for a run of Mamba layers and None for a run of attention layers.
    # Mamba-1: {"d_inner": E, "d_state": N, "dt_rank": R, "d_conv": K}.
    # Mamba-2 (SSD), told by "kind": "mamba2": {"d_inner": E = H * P,
    # "n_heads": H, "d_head": P, "n_groups": G (1: the kernels' one group),
    # "d_state": N, "d_conv": K, "chunk": the product form's chunk size}.
    # Gated DeltaNet (:class:`DeltaKind`), told by "kind": "gdn": {"d_inner":
    # E = Hv * P, "n_heads": Hv value heads, "d_head": P, "n_key_heads": Hk,
    # "d_state": N (a key head's width), "d_conv": K, "conv_dim": the
    # convolved channels (q, k and v: 2 Hk N + E), "chunk": the chunked
    # scan's chunk}. Power retention (:class:`PowerKind`), told by "kind":
    # "pr": {"d_inner": E = D, the lanes of a state (the key's expansion),
    # "d_state": N, its sublanes (Hk heads' d value channels and a normaliser
    # a head), "d_conv": 1 (no tail), "chunk", "eps": the normaliser's}
    mamba: Optional[Dict[str, Any]] = None
    # widths of compressed convolutional attention (:class:`CcaKind`; zaya):
    # {"time0", "time1": the taps of the depthwise and of the grouped
    # convolution, "conv_dim": the channels they mix (q and k of every head),
    # "tail_channels": the channels a sequence keeps a tail of (those and the
    # shifted value's), "taps": how many earlier tokens it keeps (time0 +
    # time1 - 2)}. On a run's spec it is set for a run of such layers
    cca: Optional[Dict[str, Any]] = None
    # plain multipliers (granite): on the embedding's output, on each
    # branch's output before it joins the residual stream, on the logits,
    # and the softmax scale where it is not head_dim ** -0.5. None (or the
    # neutral value) leaves the program as it is without them
    embed_scale: Optional[float] = None
    residual_scale: Optional[float] = None
    logits_scale: Optional[float] = None
    attn_scale: Optional[float] = None
    # BLOOM lineage: per-head linear position bias applied inside the paged
    # kernels (reference csrc/transformer/inference/csrc/softmax.cu) and a
    # LayerNorm right after the embedding
    alibi: bool = False
    embed_norm: bool = False
    # generation by diffusion over blocks (sdar): attention is causal by
    # BLOCKS of ``causal_block`` positions (a power of two) — key s is visible
    # to query t iff s // B <= t // B, for prompt rows and block rows alike —
    # and ``mask_token_id`` is the token a not-yet-denoised position of the
    # current block holds. 1 / None: causal by position, one token a step
    causal_block: int = 1
    mask_token_id: Optional[int] = None
    dtype: Any = jnp.bfloat16


def _run_spec(spec: RaggedModelSpec, kind) -> RaggedModelSpec:
    """The spec the layers of one ``kind`` are built with."""
    return replace(spec, layer_kinds=None, window=kind.window,
                   rope_theta=spec.rope_theta if kind.rope else None,
                   moe=spec.moe if kind.moe else None,
                   mamba=spec.mamba if kind.mamba else None,
                   cca=spec.cca if kind.tail else None,
                   block=kind.block)


def layer_runs(spec: RaggedModelSpec
               ) -> List[Tuple[RaggedModelSpec, int, int]]:
    """Maximal runs of layers of one kind, as ``(the spec that run's layers
    are built with, its first layer, how many)``. A model of one kind is one
    run under its own spec."""
    if spec.layer_kinds is None:
        return [(spec, 0, spec.num_layers)]
    runs: List[List[Any]] = []
    for l, kind in enumerate(spec.layer_kinds):
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, l, 1])
    return [(_run_spec(spec, kind), l0, n) for kind, l0, n in runs]


def _unit_cuts(kinds: Tuple[Any, ...]) -> List[Tuple[int, int, int]]:
    """``kinds`` cut into repeating units, as ``(first layer, period p,
    repeats r)``: a run of r layers of one kind (p 1), or p >= 2 kinds that
    repeat r >= 2 times. Of all such cuts, the one with the fewest layer
    BODIES to trace and compile (a unit costs its period: a scan's body runs
    each of its p layers once), then the fewest units; of equals, a single
    layer or a run before a longer period. So maximal runs stay units of
    their own where a longer period would cost more bodies than it saves
    (Jamba's ``(7 M, A, 6 M) x 2``: five runs, not a body of fourteen), a
    stretch of alternating layers becomes units of pairs (nemotron_h: ``M E M
    E M * ..``), and a period that holds a run is taken where it is cheaper
    (qwen3_next: ``(D D D A) x 3`` is one scan of four bodies, not six)."""
    n = len(kinds)
    # best[a]: ((bodies, units), cuts) for kinds[a:]
    best: Dict[int, Tuple[Tuple[int, int], List]] = {n: ((0, 0), [])}
    for a in range(n - 1, -1, -1):
        pick = None
        run = 1
        while a + run < n and kinds[a + run] == kinds[a]:
            run += 1
        for r in range(1, run + 1):
            cost, rest = best[a + r]
            cand = (cost[0] + 1, cost[1] + 1)
            if pick is None or cand < pick[0]:
                pick = (cand, [(a, 1, r)] + rest)
        for p in range(2, (n - a) // 2 + 1):
            if len(set(kinds[a:a + p])) == 1:
                continue            # a run, counted above
            r = 1
            while a + (r + 1) * p <= n and kinds[
                    a + r * p:a + (r + 1) * p] == kinds[a:a + p]:
                r += 1
            for reps in range(2, r + 1):
                cost, rest = best[a + reps * p]
                cand = (cost[0] + p, cost[1] + 1)
                if cand < pick[0]:
                    pick = (cand, [(a, p, reps)] + rest)
        best[a] = pick
    return best[0][1]


def layer_units(spec: RaggedModelSpec
                ) -> List[Tuple[Tuple[RaggedModelSpec, ...], int, int]]:
    """The layers as the layer loop scans them: ``(the specs of a unit's p
    layers, the unit's first layer, how many times it repeats)``. A unit of
    one kind is a run of layers and is scanned as ever; where every layer
    differs from the one before it (nemotron_h: ``M E M E M * E M ..``)
    maximal runs would be one scan a layer, and a unit of p kinds that
    repeats is ONE scan whose body runs the p layers in turn
    (:func:`_unit_cuts`; qwen3_next: three delta layers and an attention
    layer, three times)."""
    if spec.layer_kinds is None:
        return [((spec,), 0, spec.num_layers)]
    kinds = tuple(spec.layer_kinds)
    return [(tuple(_run_spec(spec, k) for k in kinds[l0:l0 + p]), l0, r)
            for l0, p, r in _unit_cuts(kinds)]


def describe_layer_kinds(spec: RaggedModelSpec) -> str:
    """One line for the engine's set-up log: the layers as the layer loop
    scans them (:func:`layer_units`)."""
    kinds = spec.layer_kinds

    def one(rs, l):
        if kinds is not None:
            return kinds[l].describe()
        if rs.mamba is not None:
            state = _STATE_KINDS.get(rs.mamba.get("kind"), MambaKind)
            return state(rs.moe is not None).describe()
        if rs.cca is not None:
            return CcaKind(rs.moe is not None).describe()
        return LayerKind(rs.window, rs.rope_theta is not None,
                         rs.moe is not None).describe()

    return "; ".join(
        f"layers {l0}-{l0 + len(specs) * n - 1}: "
        + (one(specs[0], l0) if len(specs) == 1 else
           f"{n} x [" + " | ".join(one(rs, l0 + k)
                                   for k, rs in enumerate(specs)) + "]")
        for specs, l0, n in layer_units(spec))


def _layer_holds(spec: RaggedModelSpec) -> List[Optional[str]]:
    """For each layer, the pool it addresses (:func:`_holds`)."""
    if spec.layer_kinds is None:
        return ["state" if spec.mamba is not None else
                "both" if spec.cca is not None else
                None if spec.block == "ffn" else "pages"] * spec.num_layers
    return [_holds(k) for k in spec.layer_kinds]


def num_state_layers(spec: RaggedModelSpec) -> int:
    """Layers that hold a slot of the state pool per sequence: Mamba mixers
    (a recurrent state and a tail) and attention that keeps a convolution
    tail beside its pages."""
    holds = _layer_holds(spec)
    return holds.count("state") + holds.count("both")


def num_page_layers(spec: RaggedModelSpec) -> int:
    """Layers that hold KV pages — THE layer count of the page pool, of a
    page's bytes and of everything counted in tokens x layers: the layers
    that attend. Not ``spec.num_layers`` where some layers carry no
    attention (a Mamba mixer holds a state, an FFN alone holds nothing)."""
    holds = _layer_holds(spec)
    return holds.count("pages") + holds.count("both")


def _pool_index(spec: RaggedModelSpec, pool: Optional[str] = None
                ) -> List[int]:
    """For each layer, its index in the pool it addresses: its rank among
    the layers of its sort (pages for attention, state for Mamba; a layer
    that addresses neither counts among its like, and nothing reads that).
    A model whose layers all hold pages addresses them by the layer's index
    in the model, as ever. A layer that addresses both pools
    (:class:`CcaKind`) counts among the layers that hold pages; with ``pool``
    named (``"pages"`` or ``"state"``) the ranks are those among the layers
    that address THAT pool, such a layer counted in each."""
    holds = _layer_holds(spec)
    sort = (lambda h: "pages" if h == "both" else h) if pool is None else \
        (lambda h: pool if h in (pool, "both") else None)
    seen: Dict[Optional[str], int] = {}
    index = []
    for h in map(sort, holds):
        index.append(seen.get(h, 0))
        seen[h] = index[-1] + 1
    return index


def _pool_bases(spec: RaggedModelSpec) -> List[int]:
    """For each run of :func:`layer_runs`, its first layer's index in the
    pool its layers address (:func:`_pool_index`)."""
    index = _pool_index(spec)
    return [index[l0] for _, l0, _ in layer_runs(spec)]


def latent_width(spec: RaggedModelSpec) -> int:
    """Values of one latent row in the pool (``spec.mla``): the latent and
    the rotary key, padded to whole lane tiles."""
    return latent_row_width(spec.mla["kv_lora_rank"],
                            spec.mla["qk_rope_head_dim"])


def index_width(spec: RaggedModelSpec) -> int:
    """Values of one index key in its pool (``spec.mla["index"]``): the
    indexer's head width in whole lane tiles."""
    return -(-spec.mla["index"]["head_dim"] // 128) * 128
