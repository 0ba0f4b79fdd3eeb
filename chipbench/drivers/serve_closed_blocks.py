"""Closed-loop serving of a model that GENERATES by diffusion over blocks
(attention causal by blocks of ``B`` positions; a block of mask tokens
denoised in place over the paged cache, then committed), with a bring-up of
its own.

The loop, the gauges and the capture in the tail are
``serve_closed_state.py``'s own (``loop``). What differs is the bring-up:

- the traffic's ``generation`` (block length, denoising steps, remasking
  rule) is laid over the engine's ``block_decode``: how many passes a token
  costs is the traffic's, not the configuration's;
- the weights are made a layer at a time (the family's ``init_params``) and
  the account is weights, headroom, pages;
- the check runs the ENGINE FIRST and the reference after it over the
  engine's own tokens, as ``serve_closed_state_moe.py`` does and for its
  reason (the model and its pool fill the chip). What the engine runs: a
  LONG prompt whose length is no multiple of ``B`` and spans two paged
  passes (the packed pass is off for this family: it does not know the block
  rule), and a SHORT one (a few blocks: there a block's own rows are a large
  share of what a row sees, so a program that masks by position shows);
  then both as rows among ``neighbours`` live ones, ``blocks`` blocks each
  through the block step IN RUNS OF THE CELL'S OWN ``decode_slice``: past a
  run's first pass a row's block is the pass before's, which never left the
  device, the host has planned masks, step and context ahead of it, and the
  drain is a pass late — the regime the window times. The pipeline records
  every pass of the two rows as it was handed to the program
  (``BlockDecodePipeline.watch``); the check rebuilds each block from those
  records alone and holds the operands to the reference's own schedule. For
  every pass of a checked row the reference is given the block as the engine
  held it BEFORE the pass (the whole sequence again: context, committed
  blocks, this block) and

  (a) its logits at the block's ``B`` rows are held against the program's:
      of the rows with a clear routing margin in the median (``tol_logits``)
      and at the 90th percentile (``tol_tail``), every row by a loose limit
      of its own (``tol_row``) — ``serve_closed_latent.py``'s statistics and
      for its reason: with a router in every layer a row may have, in some
      layer, an expert within bfloat16's rounding of the selection's edge;
  (b) the program's CHOICE — which masked positions took a token this pass,
      and which token — is the reference's rule (``denoise_choice``) on the
      PROGRAM'S OWN float32 logits of that pass, which (a) holds to the
      reference's: of the passes that had a choice of positions (more masked
      than the pass fills) the share that filled the rule's positions, and
      of all filled positions the share that took the rule's token, are both
      at least ``min_choice_share``; and a CONTROL through the same
      comparison, the reference's rule with the ranking reversed put in the
      program's place, has to read under it (the rule taken in position
      order is read too, and logged: it agrees by chance).
      Against the reference's OWN logits the choice cannot be held:
      random weights give a block's masked positions (one embedding, one
      context) confidences within a few percent of each other, less than
      bfloat16 moves them, so a fifth of the passes flip, and neither the
      reference's confidence where the program took nor its logit at the
      program's token separates a sound program from the reversed ranking
      or the float8 control; all are logged;
  (c) after a commit the next block's logits agree — they are rows of (a) —
      which they cannot if the K/V a later block attends to were a denoise
      pass's;
  (d) two controls: the reference with its activations rounded to
      ``control_act_dtype`` has to read OVER ``tol_logits`` and ``tol_tail``
      on the same rows, and the reference under the CAUSAL mask (a program
      that ignores the block rule) has to fail (a) on the short sequence's
      rows. Either passing makes the run not correct.
- off the chip (``ctx.on_chip`` false) the configuration's ``rehearsal``
  block is laid over it.
"""

import dataclasses
import gc
import importlib
import time
from typing import List

import numpy as np

from chipbench import serving
from chipbench.harness import BenchError, Context, Outcome
from chipbench.traffic import balanced, generator


def peak_gib(ctx: Context) -> str:
    """The device's peak bytes in use so far, for a log line."""
    stats = ctx.devices[0].memory_stats() if ctx.on_chip else None
    return "n/a" if not stats else \
        f"{stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB"


def bring_up(ctx: Context) -> serving.Served:
    import jax
    import jax.numpy as jnp

    overlay = ctx.registry.module("drivers", "serve_closed_kinds").overlay
    cfg = ctx.config
    if not ctx.on_chip:
        cfg = ctx.config = overlay(cfg, cfg["rehearsal"])
    family = ctx.registry.module("families", cfg["family"])
    try:
        model = family.build_model(cfg, jnp.bfloat16)
        from deepspeed_tpu.inference.v2.ragged_model import (ADAPTERS,
                                                             build_block_step)
        del build_block_step
        if model.config.family not in ADAPTERS:
            raise ImportError(f"no ragged adapter for {cfg['family']!r}")
    except ImportError as e:
        raise BenchError(f"this tree's program cannot serve the "
                         f"{cfg['family']!r} family: {e}") from None
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.inference.v2.ragged.kv_cache import KVCacheConfig
    from deepspeed_tpu.utils.tree import tree_size_bytes

    reference = importlib.import_module(
        "chipbench.reference." + family.REFERENCE)
    generation = dict(ctx.traffic["generation"])
    if generation.pop("block_length") != cfg["block_length"]:
        raise BenchError("the traffic's block length is not the "
                         "configuration's")
    dev = ctx.devices[0]
    t0 = time.time()
    params = family.init_params(model, ctx.seed, jnp.bfloat16)
    jax.block_until_ready(params)
    weight_bytes = tree_size_bytes(params)
    t1 = time.time()
    host_params = jax.device_get(params)
    del params
    gc.collect()
    ctx.log(f"weights: family {cfg['family']}, depth "
            f"{cfg['num_hidden_layers']}, {weight_bytes / 2**30:.2f} GiB "
            f"bf16, made on the device in {t1 - t0:.1f} s and moved to the "
            f"host in {time.time() - t1:.1f} s; the device's peak so far "
            f"{peak_gib(ctx)}")

    # -- the account: fill x limit, less weights and the headroom; the rest
    # is pages
    bs = cfg["engine"]["kv_cache"]["block_size"]
    limit = dev.memory_stats()["bytes_limit"] if ctx.on_chip \
        else int(cfg["rehearsal_hbm_bytes"])
    budget = int(limit * cfg["hbm_fill"]) - weight_bytes \
        - int(cfg["hbm_headroom_bytes"])
    layers, kv_heads, head_dim = family.kv_layout(cfg)
    num_blocks = KVCacheConfig.from_memory_budget(
        layers, kv_heads, head_dim, budget, block_size=bs).num_blocks
    engine_cfg = {k: dict(v) for k, v in cfg["engine"].items()}
    engine_cfg["kv_cache"]["num_blocks"] = num_blocks
    engine_cfg["block_decode"] = generation
    engine_cfg["dtype"] = jnp.bfloat16
    t1 = time.time()
    engine = InferenceEngineV2(model=model, model_parameters=host_params,
                               config=engine_cfg)
    ctx.log(f"engine: up in {time.time() - t1:.1f} s (warm-up included); "
            f"HBM limit {limit} B, weights {weight_bytes} B; {num_blocks} "
            f"pages of {bs} tokens x {layers} layers = "
            f"{engine.kv.config.bytes_per_block() * (num_blocks + 1) / 2**30:.2f}"
            f" GiB ({engine.kv.config.bytes_per_block()} B a page); blocks "
            f"of {engine.spec.causal_block}, mask token "
            f"{engine.spec.mask_token_id}, schedule {engine.block_schedule} "
            f"({generation['remasking']}); experts "
            f"{engine.spec.moe['num_experts']} top "
            f"{engine.spec.moe['top_k']}, all held; {engine.compiles} "
            f"programs; the device's peak so far {peak_gib(ctx)}")
    wrong = family.check_engine(cfg, engine)
    if wrong:
        raise BenchError(wrong)

    bad = run_check(ctx, engine, family, reference, host_params,
                    generator.rng_for(ctx.seed, "check"))
    del host_params
    gc.collect()
    if bad:
        ctx.log(f"CHECK FAILED: {bad[:8]} ({len(bad)} in all)")
    return serving.Served(
        engine=engine, vocab=int(cfg["vocab_size"]), correct=not bad,
        class_name=engine_cfg["serving"]["classes"][0]["name"])


def run_check(ctx: Context, engine, family, reference, host_params,
              rng) -> List[str]:
    """The check of the module's docstring on ``engine``; the names of what
    failed."""
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.engine_v2 import fetch_to_host

    cfg, check = ctx.config, ctx.config["check"]
    B, mask_id = int(cfg["block_length"]), int(cfg["mask_token_id"])
    Tl, Ts, NBk, NB, walk, causal_n = (int(check[k]) for k in (
        "long_prompt_tokens", "short_prompt_tokens", "blocks", "neighbours",
        "runs_a_walk", "causal_passes"))
    if not (Tl % B and Ts % B):
        raise BenchError("the check's prompts must not be whole blocks")
    sm = cfg["engine"]["state_manager"]
    if Tl - Tl % B <= sm["max_ragged_batch_size"] \
            - sm["max_ragged_sequence_count"]:
        raise BenchError("the check's long prompt fits one paged pass")
    draw = lambda n: rng.integers(0, mask_id, size=int(n)).astype(np.int32)
    prompts = {1: draw(Tl), 2: draw(Ts)}

    # -- the engine first: the prompts (whole blocks prefilled by paged
    # passes, the rest opens the first block), then the block step
    t0 = time.time()
    prefill = {u: np.asarray(engine.put([u], [p])[0], np.float32)
               for u, p in prompts.items()}
    others = list(range(3, 3 + NB))
    lo, hi = check["neighbour_tokens"]
    # (a few at a time: a put waits for nothing until its last pass, and
    # every pass enqueued holds its logits on the device from then on)
    lengths = rng.integers(lo, hi + 1, size=NB)
    for i in range(0, NB, 16):
        engine.put(others[i:i + 16], [draw(n) for n in lengths[i:i + 16]])
    half = NB // 2
    live = others[:half] + [1, 2] + others[half:]
    row_of = {1: half, 2: half + 1}
    pipe = engine.decode_pipeline(live)
    pipe.watch = [1, 2]
    static = ctx.traffic["generation"]["remasking"] == "low_confidence_static"
    schedule = reference.num_transfer_tokens(
        B, int(ctx.traffic["generation"]["denoising_steps"]))
    #: (uid, context, the block before the pass, n_take, the block after,
    #:  the program's logits [B, V])
    passes: List = []
    wrong: List[str] = []
    done = {1: 0, 2: 0}
    ctx_len = {u: len(p) - len(p) % B for u, p in prompts.items()}
    seqs = {u: [int(t) for t in p[:ctx_len[u]]] for u, p in prompts.items()}
    block = {1: None, 2: None}  # what the pass before left on the device
    denoised = {1: 0, 2: 0}     # denoise passes the open block has had
    runs = seen = 0
    while min(done.values()) < NBk:
        pipe.run(int(cfg["engine"]["serving"]["decode_slice"]))
        runs += 1
        for rec in pipe.watched[seen:]:
            u = rec["uid"]
            if done[u] >= NBk:
                continue
            # the host's block where a block opens and at a run's first
            # pass, where it is the block that came home from the run before
            if block[u] is not None and rec["fresh"] and not (
                    rec["step"] == 0
                    and (rec["fresh_ids"] == block[u]).all()) \
                    or block[u] is None and not rec["fresh"]:
                wrong.append(f"sequence {u} run {runs} pass {rec['step']}: "
                             "the block was not the one the pass before left")
            ids = rec["fresh_ids"] if rec["fresh"] else block[u]
            masks = int((ids == mask_id).sum())
            want = min(schedule[min(denoised[u], len(schedule) - 1)], masks)
            if rec["ctx"] != len(seqs[u]) or (
                    static and rec["n_take"] != want):
                wrong.append(f"sequence {u} run {runs} pass {rec['step']}: "
                             f"context {rec['ctx']} and n_take "
                             f"{rec['n_take']}; the reference's schedule "
                             f"says {len(seqs[u])} and {want}")
            passes.append((u, len(seqs[u]), ids, rec["n_take"], rec["after"],
                           np.asarray(fetch_to_host(rec["logits"]),
                                      np.float32)))
            if masks:
                block[u], denoised[u] = rec["after"], denoised[u] + 1
            else:           # the commit pass: the block as it is
                seqs[u] += [int(t) for t in ids]
                block[u], denoised[u], done[u] = None, 0, done[u] + 1
        seen = len(pipe.watched)
    pipe.watch = ()
    del pipe.watched[:]
    engine.flush(live)
    free = engine.free_blocks
    if free != engine.allocator.total_blocks:
        raise BenchError(f"{engine.allocator.total_blocks - free} pages were "
                         "not given back after the check's sequences left")
    ctx.log(f"check: the engine ran prompts of {Tl} and {Ts} tokens (paged "
            f"passes) and {len(passes)} block-step passes of the two checked "
            f"rows ({NBk} blocks each) as rows {row_of[1]} and {row_of[2]} "
            f"of {len(live)} live sequences, in {runs} runs of "
            f"{cfg['engine']['serving']['decode_slice']} passes, in "
            f"{time.time() - t0:.1f} s; "
            f"the device's peak so far {peak_gib(ctx)}")

    # -- then the reference over those very tokens: the whole sequence again
    # for every pass, padded with mask tokens to one length a sequence (a
    # later block is invisible to an earlier row), float32 and the control;
    # the causal control on the short sequence's first passes
    t0 = time.time()
    hp = family.reference_hp(cfg)
    weights = family.reference_weights(host_params, cfg)
    length = {u: ctx_len[u] + NBk * B for u in (1, 2)}
    low = getattr(jnp, check["control_act_dtype"])
    runs, of = [], []
    for n, (u, at, ids, _, _, _) in enumerate(passes):
        seq = np.full((length[u],), mask_id, np.int32)
        seq[:at] = seqs[u][:at]
        seq[at:at + B] = ids
        rows = np.arange(at, at + B)
        if at == ctx_len[u] and not any(p[0] == u for p in passes[:n]):
            # the first pass of a sequence also gives the prompt's last
            # prefilled row, which the paged passes' logits are held to
            rows = np.concatenate([[at - 1], rows])
        for kind, extra in (("ref", {}), ("low", {"act_dtype": low})) + (
                (("causal", {"causal": True}),) if u == 2 and sum(
                    1 for k, m, _ in of if k == "causal") < causal_n else ()):
            runs.append(dict(ids=seq, rows=rows, **extra))
            of.append((kind, n, len(rows) - B))
    out = []
    for i in range(0, len(runs), walk):
        out += [(np.asarray(lg, np.float32), np.asarray(m, np.float32))
                for lg, m in reference.forward_many(weights, runs[i:i + walk],
                                                    hp)]
    del weights
    got_of = {"ref": {}, "low": {}, "causal": {}}
    for (kind, n, lead), (lg, margin) in zip(of, out):
        got_of[kind][n] = (lg, margin, lead)
    ctx.log(f"reference: {len(runs)} whole-sequence forwards ({len(passes)} "
            f"in float32, as many with {check['control_act_dtype']} "
            f"activations, {len(got_of['causal'])} under the causal mask) in "
            f"walks of {walk} in {time.time() - t0:.1f} s; the device's peak "
            f"so far {peak_gib(ctx)}")

    tol, tol_tail, tol_row, min_share = (float(check[k]) for k in (
        "tol_logits", "tol_tail", "tol_row", "min_choice_share"))
    floor = float(check["min_routing_margin"])
    bad: List[str] = wrong
    errs, ctl, every, causal_errs, causal_own = [], [], [], [], []
    by_row: List = []       # (sequence, margin, rel err) of every block row
    rule = {"mask_token_id": mask_id}
    if ctx.traffic["generation"]["remasking"] == "low_confidence_dynamic":
        rule["threshold"] = float(ctx.traffic["generation"].get(
            "confidence_threshold", 0.9))
    for n, (u, at, ids, n_take, after, got) in enumerate(passes):
        ref, margin, lead = got_of["ref"][n]
        if not np.isfinite(ref).all():
            raise BenchError("the reference's logits are not finite")
        name = f"sequence {u} context {at} pass {n}"
        if lead:            # the paged passes' logits, at the last row
            err = serving.rel_err(prefill[u], ref[0])
            ctx.log(f"check prefill of sequence {u} (paged passes), row "
                    f"{at - 1}: rel err {err:.2e} (a row's limit "
                    f"{tol_row:.1e}; margin {margin[0]:.1e})")
            if not (np.isfinite(prefill[u]).all() and err <= tol_row):
                bad.append(f"prefill of sequence {u}")
            if margin[0] >= floor:
                errs.append(err)
                ctl.append(serving.rel_err(got_of["low"][n][0][0], ref[0]))
        ref, margin = ref[lead:], margin[lead:]
        low_lg = got_of["low"][n][0][lead:]
        for r in range(B):                                  # (a)
            err = serving.rel_err(got[r], ref[r])
            every.append(err)
            by_row.append((u, float(margin[r]), err))
            if not (np.isfinite(got[r]).all() and err <= tol_row):
                ctx.log(f"check {name} row {r}: rel err {err:.2e} over a "
                        f"row's limit {tol_row:.1e} (margin {margin[r]:.1e})")
                bad.append(f"{name} row {r}")
            if margin[r] >= floor:
                errs.append(err)
                ctl.append(serving.rel_err(low_lg[r], ref[r]))
            if n in got_of["causal"]:
                causal_errs.append(serving.rel_err(
                    got_of["causal"][n][0][r], ref[r]))
                causal_own.append(err)
    bad += choice_check(ctx, reference, rule, passes, got_of, min_share)
    if len(errs) < int(check["min_rows"]):
        raise BenchError(f"fewer than {check['min_rows']} check rows have a "
                         "clear routing margin; choose another seed")
    stats = lambda v: (float(np.median(v)), float(np.percentile(v, 90)))
    (median, p90), (ctl_median, ctl_p90) = stats(errs), stats(ctl)
    ctx.log(f"check: {len(every)} block rows of {len(passes)} passes and 2 "
            f"prefill rows, {len(errs)} of them with a clear margin: their "
            f"median rel err {median:.2e} (tol {tol:.1e}), 90th percentile "
            f"{p90:.2e} (tol {tol_tail:.1e}), largest {max(errs):.2e} (of "
            f"all block rows: median {float(np.median(every)):.2e}, largest "
            f"{max(every):.2e}); the control reads {ctl_median:.2e} and "
            f"{ctl_p90:.2e} on the same rows")
    # for people: where the error sits, by sequence and by margin
    for u in (1, 2):
        mine = np.array([e for v, m, e in by_row if v == u and m >= floor])
        ctx.log(f"check: sequence {u}: {len(mine)} clear rows, median "
                f"{np.median(mine):.2e}, 90th percentile "
                f"{np.percentile(mine, 90):.2e}, largest {mine.max():.2e}")
    for lo_m, hi_m in ((0, floor), (floor, 5 * floor), (5 * floor, 1e9)):
        mine = [e for _, m, e in by_row if lo_m <= m < hi_m]
        if mine:
            ctx.log(f"check: rows with margin in [{lo_m:.0e}, {hi_m:.0e}): "
                    f"{len(mine)}, median {np.median(mine):.2e}, largest "
                    f"{max(mine):.2e}")
    if not median <= tol:
        bad.append("the median of the compared rows")
    if not p90 <= tol_tail:
        bad.append("the 90th percentile of the compared rows")
    if not (ctl_median > tol and ctl_p90 > tol_tail):
        bad.append(f"logits control (the reference in "
                   f"{check['control_act_dtype']} passes)")
    c_median = float(np.median(causal_errs)) if causal_errs else 0.0
    ctx.log(f"check: the reference under the CAUSAL mask reads a median of "
            f"{c_median:.2e} over the short sequence's {len(causal_errs)} "
            f"rows (the program on them: "
            f"{float(np.median(causal_own or [0.0])):.2e}; tol {tol:.1e})")
    if not c_median > tol:
        bad.append("causal control (a program that ignores the block rule "
                   "passes)")
    return bad


def choice_check(ctx: Context, reference, rule, passes, got_of,
                 min_share: float) -> List[str]:
    """(b) of the module's docstring over the denoise passes of ``passes``;
    the names of what failed."""
    mask_id = rule["mask_token_id"]
    denoise = [(n, p) for n, p in enumerate(passes) if p[3] > 0]

    def filled(ids, after):
        return sorted(int(i) for i in np.flatnonzero(
            (ids == mask_id) & (np.asarray(after) != mask_id)))

    # the reference's rule on the program's own logits
    own = {n: reference.denoise_choice(got, ids, n_take, rule)[0]
           for n, (_, _, ids, n_take, _, got) in denoise}

    def shares(after_of):
        """With ``after_of(n)`` in the program's place: the share of the
        passes that had a choice of positions which filled the rule's, and
        the share of filled positions that took the rule's token."""
        chose = placed = tokens = right = 0
        for n, (_, _, ids, n_take, _, _) in denoise:
            after = np.asarray(after_of(n))
            took = filled(ids, after)
            if n_take < int((ids == mask_id).sum()):
                chose += 1
                placed += took == filled(ids, own[n])
            tokens += len(took)
            right += sum(int(after[i]) == int(own[n][i]) for i in took)
        return placed / max(chose, 1), right / max(tokens, 1), chose, tokens

    def control(order):
        return lambda n: reference.denoise_choice(
            passes[n][5], passes[n][2], passes[n][3],
            dict(rule, order=order))[0]

    bad: List[str] = []
    placed, right, chose, tokens = shares(lambda n: passes[n][4])
    ctx.log(f"check: the choice, against the reference's rule on the "
            f"program's own logits: {chose} passes had a choice of positions "
            f"and {placed:.3f} of them filled the rule's; {tokens} positions "
            f"were filled, {right:.3f} of them with the rule's token (both "
            f"at least {min_share})")
    if not (chose and placed >= min_share and right >= min_share):
        bad.append("the choice (positions or tokens are not the rule's on "
                   "the program's own logits)")
    # the reversed ranking decides (it fills the other positions: 0 by
    # construction); the ranking by position is a second reading, logged —
    # it agrees with the rule by chance, and by the seed's weights more in
    # one run than another
    c_placed = shares(control("least"))[0]
    ctx.log(f"check: the rule ranked the wrong way round in the program's "
            f"place fills the rule's positions in {c_placed:.3f} of those "
            f"passes (has to read under {min_share}); ranked by position, in "
            f"{shares(control('position'))[0]:.3f}")
    if not c_placed < min_share:
        bad.append("choice control (the rule ranked the wrong way round "
                   "passes)")

    # against the reference's own logits, for people: flips, confidences,
    # tokens
    same = flips = 0
    worst = {"program": 0.0, "least": 0.0}
    gaps, low_gaps = [], []
    for n, (_, _, ids, n_take, after, _) in denoise:
        ref, _, lead = got_of["ref"][n]
        ref, low = ref[lead:], got_of["low"][n][0][lead:]
        want_ids, _, conf, want = reference.denoise_choice(ref, ids, n_take,
                                                           rule)
        took = filled(ids, after)
        if took == want and all(int(after[i]) == int(want_ids[i])
                                for i in took):
            same += 1
        else:
            flips += 1
        least = min((conf[i] for i in want), default=0.0)
        # ln(the reference's least taken confidence / its confidence where
        # another choice took): the program's, and the reversed ranking's
        for who, other in (("program", took),
                           ("least", filled(ids, control("least")(n)))):
            worst[who] = max([worst[who]] + [
                float(np.log(least / conf[i])) for i in other if conf[i] > 0])
        scale = float(np.max(np.abs(ref)))
        low_x0 = reference.denoise_choice(low, ids, n_take, rule)[1]
        for i in took:
            gap = float(ref[i].max() - ref[i][int(after[i])]) / scale
            gaps.append(gap)
            low_gaps.append(float(ref[i].max() - ref[i][int(low_x0[i])])
                            / scale)
    ctx.log(f"check: against the reference's own logits {same} of "
            f"{len(denoise)} denoise passes chose alike and {flips} flipped; "
            f"ln(the reference's least taken confidence / its confidence "
            f"where another choice took) reads at most "
            f"{worst['program']:.3f} for the program and "
            f"{worst['least']:.3f} for the reversed ranking; the program's "
            f"tokens lie at most {max(gaps):.3f} of the scale under the "
            f"reference's best (median {float(np.median(gaps)):.3f}), the "
            f"{ctx.config['check']['control_act_dtype']} control's "
            f"{min(low_gaps):.3f} to {max(low_gaps):.3f} (median "
            f"{float(np.median(low_gaps)):.3f}): logged, not held — neither "
            "separates a sound program from a wrong one at these widths")
    return bad


def serve(ctx: Context, served: serving.Served) -> Outcome:
    """The closed loop over ``served``: ``serve_closed_state.py``'s."""
    mix = ctx.traffic
    if not ctx.on_chip:
        overlay = ctx.registry.module("drivers", "serve_closed_kinds").overlay
        mix = ctx.traffic = overlay(mix, ctx.config.get(
            "rehearsal_traffic", {}))
    loop = ctx.registry.module("drivers", "serve_closed_state").loop
    # prompts draw their ids below the mask token, so it is never a prompt
    # token; what is generated may be any token of the vocabulary but it
    below = int(ctx.config["mask_token_id"])
    pool = balanced.closed_pool(mix, ctx.seed, below)
    with served.engine.serving_frontend() as frontend:
        serving.warm_traffic(ctx, dataclasses.replace(served, vocab=below),
                             frontend)
        got = loop(ctx, served, frontend, mix, pool, float(ctx.seconds),
                   ctx.tracer, ctx.capture)
    return Outcome(correct=served.correct and got["failed"] == 0,
                   attempted=got["attempted"], failed=got["failed"],
                   window_start=got["window_start"], end_to_end=got["values"],
                   counters=got["counters"])


def run(ctx: Context) -> Outcome:
    return serve(ctx, bring_up(ctx))
