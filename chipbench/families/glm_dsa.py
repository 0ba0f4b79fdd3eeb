"""The glm_dsa family (GLM-5, ``glm_moe_dsa``) for the benchmark: from a
configuration file to the program's model, the program's weights under the
names of the plain reference (``chipbench/reference/glm_dsa_ref.py``), and
the selection's own check.

It is the joyai family's protocol (``chipbench/families/joyai.py``: latent
attention, a sigmoid router, one chip's share of the experts) for
``drivers/serve_closed_latent.py``, with what the indexer adds: four weights
a layer, an index key a token a layer beside the latent row in
``page_layout`` (``latent_dim`` is what the budget funds a token a layer:
both pools' values), :func:`selection_readings`, which
``drivers/serve_closed_selected.py`` runs after the bring-up, and
:func:`balance`: ``init_params`` sets each router's bias on the model's own
hidden states, so that the share of the choices this chip's experts take is
the deployment's and not the seed's.
"""

from __future__ import annotations

import math
from typing import Any, Dict

from chipbench.families import joyai as _joyai

#: module under chipbench/reference with forward_logits(weights, ids, hp,
#: rows=, with_margin=, act_dtype=) and index_readings(...)
REFERENCE = "glm_dsa_ref"

MODEL_KEYS = tuple(k for k in _joyai.MODEL_KEYS if k != "rope_theta") + (
    "index_n_heads", "index_head_dim", "index_topk")

experts = _joyai.experts
router_readings = _joyai.router_readings
held_touched_share = _joyai.held_touched_share


def assumed_numbers(cfg: Dict[str, Any]) -> Dict[str, Any]:
    return cfg.get("assumed_numbers", {})


def build_model(cfg: Dict[str, Any], dtype):
    """The program's flax module for configuration file ``cfg``."""
    from deepspeed_tpu.models.glm_dsa import GlmDsaConfig, GlmDsaForCausalLM
    rope = cfg["rope_parameters"]
    if cfg.get("hidden_act", "silu") != "silu" or cfg.get(
            "tie_word_embeddings", False) or cfg.get("attention_bias", False) \
            or rope.get("rope_type", "default") != "default" \
            or not cfg.get("indexer_rope_interleave", True) \
            or not cfg.get("rope_interleave", True):
        raise ValueError("the reference covers SwiGLU, an untied head, "
                         "bias-free attention, unscaled rotary frequencies "
                         "and interleaved rotary pairs only")
    keys = {k: cfg[k] for k in MODEL_KEYS if k in cfg}
    width, held = experts(cfg)
    return GlmDsaForCausalLM(GlmDsaConfig(
        **keys, rope_theta=float(rope["rope_theta"]), n_routed_experts=width,
        index_norm_eps=float(assumed_numbers(cfg).get("index_norm_eps",
                                                      1e-6)),
        experts_held=None if held[1] == width else held, dtype=dtype))


def init_params(model, seed: int, dtype):
    """Random weights from the seed, made on the device a layer at a time
    (``families/joyai.py::init_params``: one small program a kind of layer
    and one for the embedding, the final norm and the head)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from chipbench import models
    from deepspeed_tpu.models.glm_dsa import GlmDsaBlock, GlmDsaForCausalLM
    from deepspeed_tpu.utils.tree import tree_cast

    cfg = model.config
    key = jax.random.wrap_key_data(
        jnp.tile(jax.random.key_data(models.jax_key(seed)), 2), impl="rbg")
    probe = jnp.zeros((1, 8), jnp.int32)
    x = jnp.zeros((1, 8, cfg.hidden_size), dtype)
    ends = GlmDsaForCausalLM(dataclasses.replace(cfg, num_hidden_layers=0))
    params = dict(jax.jit(lambda k: tree_cast(
        ends.init(k, probe)["params"], dtype))(
            jax.random.fold_in(key, cfg.num_hidden_layers)))
    made = {}
    for i in range(cfg.num_hidden_layers):
        moe = cfg.is_moe_layer(i)
        if moe not in made:
            made[moe] = jax.jit(lambda k, i=i: tree_cast(
                GlmDsaBlock(cfg, i).init(k, x, probe)["params"], dtype))
        params[f"layers_{i}"] = made[moe](jax.random.fold_in(key, i))
    balance(params, cfg, jax.random.fold_in(key, cfg.num_hidden_layers + 1))
    return params


def balance(params, cfg, key, batch: int = 8, tokens: int = 512,
            steps: int = 200) -> None:
    """Each MoE layer's ``e_score_correction_bias`` set as a trained
    ``noaux_tc`` router's is: by the bias-only update (``bias += rate *
    (1 - load)``, load in units of the even share; no gradient, no other
    weight touched) on the model's OWN hidden states — ``batch`` sequences of
    ``tokens`` random ids through the zoo's dense forward, layer ``l``'s
    states those the balanced layers below it give — until every expert is
    chosen about as often as every other. A random router over a random
    model's states favours a few experts, which ones by the seed: the share
    of a pass's choices that falls on this chip's 16 of 256 then differs
    from seed to seed (a 6 s capture counted 2 to 117 second turns of the
    held share's compact path, and the same 16 prompts prefilled in 22.5 to
    23.2 s: PERF.md, PR 57), where a deployment's router sends every expert
    its share. In place, like ``families/zaya.py::balance``."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.glm_dsa import GlmDsaBlock

    E, k = cfg.n_routed_experts, cfg.num_experts_per_tok
    f32 = jnp.float32
    ids = jax.random.randint(key, (batch, tokens), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(tokens)[None], (batch, tokens))
    x = jnp.take(params["embed_tokens"]["embedding"], ids, axis=0)
    router_input = lambda mdl, _: mdl.name == "post_attention_layernorm"

    def layer(i):
        block = GlmDsaBlock(cfg, i)

        @jax.jit
        def run(p, x):
            y, seen = block.apply({"params": p}, x, positions,
                                  capture_intermediates=router_input,
                                  mutable=["intermediates"])
            return y, seen["intermediates"]["post_attention_layernorm"][
                "__call__"][0]
        return run

    @jax.jit
    def fit(h, gate):
        scores = jax.nn.sigmoid(h.reshape(-1, h.shape[-1]).astype(f32)
                                @ gate.astype(f32))

        def step(s, beta):
            _, chosen = jax.lax.top_k(scores + beta, k)
            load = jnp.mean(jnp.sum(jax.nn.one_hot(chosen, E, dtype=f32),
                                    axis=1), axis=0) * (E / k)
            rate = 0.03 * (1.0 - s / steps) + 0.002
            return beta + rate * jnp.clip(1.0 - load, -1.0, 1.0)

        beta = jax.lax.fori_loop(0, steps, step, jnp.zeros((E,), f32))
        return beta - jnp.mean(beta)

    runs = {}
    for i in range(cfg.num_hidden_layers):
        moe = cfg.is_moe_layer(i)
        run = runs.setdefault(moe, layer(i))
        p = params[f"layers_{i}"]
        y, h = run(p, x)
        if moe:
            bias = p["mlp"]["e_score_correction_bias"]
            p["mlp"]["e_score_correction_bias"] = fit(
                h, p["mlp"]["gate"]["kernel"]).astype(bias.dtype)
            y, _ = run(p, x)
        x = y


def _tiles(n: int) -> int:
    return -(-n // 128) * 128


def page_layout(cfg: Dict[str, Any]) -> Dict[str, int]:
    """What the pools hold of a token a layer: a latent row (the latent and
    the one rotary key, in whole 128-lane tiles) in one pool and an index
    key in the other, under the same page ids; ``latent_dim`` is their sum,
    what ``KVCacheConfig.from_memory_budget`` funds a token a layer."""
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    latent, index = _tiles(row), _tiles(cfg["index_head_dim"])
    return {"layers": cfg["num_hidden_layers"], "row_values": row,
            "latent_row_dim": latent, "index_dim": index,
            "latent_dim": latent + index}


def reference_hp(cfg: Dict[str, Any]) -> Dict[str, Any]:
    hp = _joyai.reference_hp(dict(
        cfg, rope_theta=cfg["rope_parameters"]["rope_theta"]))
    hp.update(index_heads=cfg["index_n_heads"],
              index_head_dim=cfg["index_head_dim"],
              index_rope_dim=cfg["qk_rope_head_dim"],
              index_topk=cfg["index_topk"],
              index_eps=float(assumed_numbers(cfg).get("index_norm_eps",
                                                       1e-6)))
    return hp


def _index_weights(ix: Dict[str, Any]) -> Dict[str, Any]:
    return {"wq": ix["wq_b"]["kernel"], "wk": ix["wk"]["kernel"],
            "k_norm": ix["k_norm"]["scale"], "k_bias": ix["k_norm"]["bias"],
            "ww": ix["weights_proj"]["kernel"]}


def reference_weights(params: Dict[str, Any], cfg: Dict[str, Any]
                      ) -> Dict[str, Any]:
    """The zoo's parameter tree under the reference's names (no copy)."""
    weights = _joyai.reference_weights(params, cfg)
    for i, layer in enumerate(weights["layers"]):
        layer["index"] = _index_weights(
            params[f"layers_{i}"]["self_attn"]["indexer"])
    return weights


def check_engine(cfg: Dict[str, Any], engine) -> str:
    """What is wrong with the engine against the configuration, or ''."""
    spec, layout = engine.spec, page_layout(cfg)
    if spec.mla is None or "index" not in spec.mla:
        return "the engine does not select inside latent attention"
    ix = spec.mla["index"]
    want_ix = (cfg["index_n_heads"], cfg["index_head_dim"], cfg["index_topk"],
               cfg["qk_rope_head_dim"])
    if (ix["heads"], ix["head_dim"], ix["topk"], ix["rope_dim"]) != want_ix:
        return f"the engine's indexer is {ix}, the file says {want_ix}"
    pools = engine.kv.kv
    lead = (layout["layers"], engine.kv.config.num_blocks,
            cfg["engine"]["kv_cache"]["block_size"])
    want = (lead + (layout["latent_row_dim"],), lead + (layout["index_dim"],))
    if not isinstance(pools, tuple) or tuple(
            tuple(p.shape) for p in pools) != want:
        return "the pools are not latent rows and index keys " + str(want)
    page = math.prod(lead[::2]) * layout["latent_dim"] * pools[0].dtype.itemsize
    if engine.kv.config.bytes_per_block() != page:
        return (f"a page is {engine.kv.config.bytes_per_block()} B, the "
                f"layout funds {page}")
    # the rest is the joyai family's: MoE layers, the router's width, the
    # share held (its pool test is the one above)
    dense = cfg["first_k_dense_replace"]
    got = [bool(k.moe) for k in spec.layer_kinds or ()]
    if got != [i >= dense for i in range(cfg["num_hidden_layers"])]:
        return f"the engine's MoE layers are {got}"
    width, held = experts(cfg)
    if spec.moe["num_experts"] != width or spec.moe.get(
            "held", (0, width)) != held:
        return (f"the engine routes over {spec.moe['num_experts']} experts "
                f"and holds {spec.moe.get('held')}; the file says {width} "
                f"and {held}")
    stack = engine.weights["layers"][-1]
    if stack["moe"]["w_gate"].shape[1] != held[1] \
            or stack["moe"]["router"].shape[-1] != width:
        return "the expert stacks or the router have another width"
    return ""


def selection_readings(engine, reference, hp: Dict[str, Any],
                       check: Dict[str, Any], rng) -> Dict[str, Any]:
    """The engine's indexer and selection by themselves, through the very
    functions its three programs' layers call, on the device the engine runs
    on, with the engine's own indexer weights of its LAST layer:
    ``ragged_model._index_project`` on ``max(index_contexts)`` rows of
    unit-normal inputs, the keys laid out as pages under a SHUFFLED block
    table, then

    - ``ragged_mla.select_chunk`` as a paged pass calls it: one chunk slot
      of the engine's chunk size a context, ending at it;
    - ``ragged_mla.select_decode`` as a paged pass calls it for a decode
      row (the pages hold the row's own key): one row a context, at the
      context's last position;
    - ``ragged_mla.select_decode`` as the fused decode step calls it: the
      same rows with their own key handed beside pages that hold the prefix
      only (the own position's key in the row's pages is spoilt here);

    against ``reference.index_readings`` on the same inputs. The decode
    rows' selections are read from what ``select_decode`` GATHERED, out of
    a table that holds each physical row's position: the transposed
    one-slot ``select``, ``chosen_positions``, the page lookup and the
    gather are all in what is compared.

    A position's distance from a row's threshold (the smallest score the
    reference keeps) is counted in units of the spread (standard deviation)
    of the scores that row sees. ``worst``: the largest such distance among
    the positions that one side keeps and the other does not (``flipped`` of
    them among ``kept``, chunk slots); ``differ``: how many lie further than
    ``tol_index`` — scores closer than that to the edge are rounding's to
    place; ``miscounted``: rows that keep another NUMBER of positions than
    the reference (a threshold off by one, a position gathered twice).
    ``control`` / ``control_worst``: the same for the reference against
    itself with its index queries, keys and scores rounded to
    ``index_control_dtype``: ``control`` has to be over 0."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.inference.v2 import ragged_mla, ragged_model
    from deepspeed_tpu.ops.pallas import sparse_mla

    spec = ragged_model.layer_runs(engine.spec)[-1][0]
    stack = engine.weights["layers"][-1]
    wi = jax.tree_util.tree_map(lambda a: a[-1], stack["index"])
    contexts = [int(c) for c in check["index_contexts"]]
    S, n = max(contexts), len(contexts)
    Cs = engine.config.state_manager.chunk_slot_size
    bs = engine.kv.config.block_size
    dtype = engine.kv.config.dtype
    tol = float(check["tol_index"])
    assert S % bs == 0 and all(c >= Cs for c in contexts)
    NP = S // bs

    h = jnp.asarray(rng.standard_normal((S, spec.hidden_size)), dtype)
    q_rows = np.concatenate([np.arange(c - Cs, c) for c in contexts])
    cq = jnp.asarray(rng.standard_normal((len(q_rows),
                                          spec.mla["q_lora_rank"])), dtype)
    rows = jnp.asarray(q_rows, jnp.int32)
    # logical page j lies at physical page perm[j]; behind the NP pages one
    # more a decode row: a copy of the page of the row's own position
    perm = jnp.asarray(rng.permutation(NP), jnp.int32)
    ctx = jnp.asarray(contexts, jnp.int32)
    last = ctx - 1
    own_at = NP + jnp.arange(n, dtype=jnp.int32)
    inpage = jnp.arange(bs, dtype=jnp.int32)
    where = jnp.zeros(((NP + n) * bs, 1), jnp.int32)
    where = where.at[(perm[:, None] * bs + inpage).reshape(-1), 0].set(
        jnp.arange(S, dtype=jnp.int32))
    where = where.at[(own_at[:, None] * bs + inpage).reshape(-1), 0].set(
        ((last // bs)[:, None] * bs + inpage).reshape(-1))

    @jax.jit
    def program(h, cq):
        # every row's key (queries of zeros: only the keys are read), then
        # the query rows' queries and weights at their own positions
        _, _, keys = ragged_model._index_project(
            spec, wi, h, jnp.zeros((S, cq.shape[1]), dtype),
            jnp.arange(S, dtype=jnp.int32))
        q, w, _ = ragged_model._index_project(spec, wi, h[rows], cq, rows)
        logical = keys.reshape(NP, bs, -1)
        spoilt = logical[last // bs].at[jnp.arange(n), last % bs].set(
            jnp.asarray(7.0, keys.dtype))
        pages = jnp.zeros((NP + n,) + logical.shape[1:], keys.dtype)
        pages = pages.at[perm].set(logical).at[own_at].set(spoilt)
        bt = jnp.broadcast_to(perm[None], (n, NP))
        bt_own = bt.at[jnp.arange(n), last // bs].set(own_at)
        qc = q.reshape((n, Cs) + q.shape[1:])
        wc = w.reshape(n, Cs, -1)
        sc, thr, pcut = ragged_mla.select_chunk(spec, qc, wc, pages, bt,
                                                ctx - Cs, ctx)
        keep_c = sparse_mla.keep_mask(sc, thr, pcut)[..., :S]
        got, live, _ = ragged_mla.select_decode(
            spec, qc[:, -1], wc[:, -1], None, pages, where, bt, last, ctx)
        got_own, live_own, own_on = ragged_mla.select_decode(
            spec, qc[:, -1], wc[:, -1], keys[last], pages, where, bt_own,
            last, last)
        return (keep_c.reshape(-1, S), (got[..., 0], live),
                (got_own[..., 0], live_own, own_on))

    keep_c, in_pass, in_step = program(h, cq)

    def gathered(got, live, own_on=None):
        """(the positions a decode row's gather brought, as a mask [n, S];
        how many the row says it keeps)."""
        got, live = np.asarray(got), np.asarray(live)
        mask = np.zeros((n, S), bool)
        for i in range(n):
            mask[i, got[i, :live[i]]] = True
        if own_on is not None:
            mask[np.arange(n), np.asarray(last)] |= np.asarray(own_on) > 0
            live = live + np.asarray(own_on)
        return jnp.asarray(mask), live

    items = lambda d: tuple(sorted(d.items()))
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    ref_ix = {k: f32(v) for k, v in wi.items()}
    scores, want, thr = reference.index_readings(
        ref_ix, f32(h), f32(cq), rows, items(hp))
    low = dict(hp, index_dtype=getattr(jnp, check["index_control_dtype"]))
    _, rounded, _ = reference.index_readings(
        ref_ix, f32(h), f32(cq), rows, items(low))
    # how far a position's score lies from the row's threshold, in units of
    # the spread of the scores the row sees
    seen = jnp.isfinite(scores)
    cnt = jnp.sum(seen, axis=-1, keepdims=True)
    mean = jnp.sum(jnp.where(seen, scores, 0.0), axis=-1, keepdims=True) / cnt
    spread = jnp.sqrt(jnp.sum(jnp.where(seen, (scores - mean) ** 2, 0.0),
                              axis=-1, keepdims=True) / cnt)
    away = jnp.where(seen, jnp.abs(scores - thr[:, None]) / spread, 0.0)
    ends = np.arange(Cs - 1, len(q_rows), Cs)

    def reading(got, rows=slice(None)):
        """(positions kept by one side only further than ``tol`` from the
        threshold, the furthest such position's distance)."""
        off = jnp.where(got != want[rows], away[rows], 0.0)
        return int(jnp.sum(off > tol)), float(jnp.max(off))

    differ, worst = reading(keep_c)
    # a row keeps exactly min(topk, what it sees): a threshold off by one
    # moves the key AT the threshold, which no distance from it shows
    miscounted = int(jnp.sum(keep_c.sum(-1) != want.sum(-1)))
    for mask, said in (gathered(*in_pass), gathered(*in_step)):
        differ_d, worst_d = reading(mask, ends)
        differ, worst = differ + differ_d, max(worst, worst_d)
        kept = np.asarray(want[ends].sum(-1))
        miscounted += int(np.sum((np.asarray(mask.sum(-1)) != kept)
                                 | (said != kept)))
    control, control_worst = reading(rounded)
    return {"rows": int(len(q_rows)), "contexts": contexts,
            "kept": int(jnp.sum(want)), "differ": differ,
            "miscounted": miscounted,
            "flipped": int(jnp.sum(keep_c != want)),
            "worst": worst, "control": control,
            "control_worst": control_worst}
