"""Training step-loop benchmark: synchronous loop vs the async pipeline.

Parity role: the serving side has ``serving_bench.py --steady-state`` holding
the decode pipeline's overlap honest; this is the same harness for the
TRAINING hot path (the ROADMAP's core workload). Two workload legs, each
driving the SAME engine over the SAME data order through two orchestrations:

- **sync**: the pre-PR step loop — the dataloader collates the global batch
  item-by-item on the caller's thread, ``train_batch`` stages it inline
  (host->device ``device_put`` on the critical path), and the loss is
  ``float()``'d immediately, blocking on the just-dispatched step. One full
  serialisation per step.
- **pipelined**: ``PrefetchLoader`` stages device-resident sharded batches
  from a producer thread and ``engine.train_steps`` keeps dispatching fused
  steps while metrics ride one step behind, materialised once at the end.

Legs:

- ``lm``: tiny GPT2 over text items TOKENIZED IN COLLATE (a pure-python
  byte-BPE stand-in for the real tokenizers that run in input pipelines) —
  pad + shifted labels + mask. On a 2-core CPU box the producer's python
  shares the GIL with the consumer, so the overlap win here is modest
  (~1.2x); on a real TPU host the device side costs no host CPU at all and
  the full producer/consumer overlap applies.
- ``host_bound``: the input-bandwidth-bound regime prefetch pipelines exist
  for (t5x prefetch-to-device, tf.data) — feature batches (``[seq, feat]``
  float32 items) whose collate+staging is C-level memcpy comparable to the
  cheap device step. This is the acceptance-gate leg: the host work is
  GIL-free, so the producer genuinely overlaps the device and the pipeline
  clears >=1.3x on the 2-core container.
- ``offload_cpu`` / ``offload_nvme`` (``--offload``): the OFFLOADED
  OPTIMIZER pipeline (docs/TRAINING.md "Offloaded optimizer pipeline").
  Param-heavy/flops-light model (the ZeRO-Offload regime) driven through
  the SAME engine twice per rep: ``overlap_step`` flipped OFF (the pre-PR
  serial fetch-all/step-all/upload-all host step) vs ON (the three-stage
  fetch/step/upload group pipeline, threaded host kernel, NVMe swapper
  double-buffering underneath). Same gates: byte-identical per-step loss
  streams (host kernels are elementwise; the device program is shared, so
  equality is structural — a pipeline bug breaks it) and zero timed-run
  compiles. The nvme leg additionally reports ``swap_ms_per_step`` — the
  pure IO cost that bounds how much slower than the cpu leg it may run.

Correctness gates on BOTH legs (exit 1 on violation — throughput is
reported, the >=1.3x bar applies to the host_bound leg's median):

- per-step loss streams BYTE-IDENTICAL between the orchestrations (same
  math, different orchestration; engine state is snapshot/restored between
  legs so every run starts from the same parameters), and stable across
  repeats;
- zero XLA compiles during the timed runs (``engine.compiles``; warmup
  rounds pay them).

Usage:
  python benchmarks/train_bench.py [--steps 30] [--reps 3] [--smoke]
                                   [--legs lm,host_bound] [--prefetch 2]
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

# runnable as `python benchmarks/train_bench.py` from a bare checkout
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LM_SEQ = 32
LM_VOCAB = 256
_TEXT = "the quick brown fox jumps over the lazy dog " * 8


def _bpe_ish(text: str):
    """Pure-python byte-pair-ish tokenizer: three greedy merge rounds over
    the utf-8 bytes. A stand-in for the per-item python cost (HF tokenizers,
    augmentation) real input pipelines pay on the caller's thread."""
    toks = list(text.encode("utf-8"))
    for _ in range(3):
        out, i, n = [], 0, len(toks)
        while i < n:
            if i + 1 < n and (toks[i] * 31 ^ toks[i + 1]) % 7 == 0:
                out.append((toks[i] * 31 + toks[i + 1]) % LM_VOCAB)
                i += 2
            else:
                out.append(toks[i] % LM_VOCAB)
                i += 1
        toks = out
    return toks


def lm_collate(items):
    """Tokenize + pad + shifted labels + mask — the LM input pipeline."""
    ids = np.zeros((len(items), LM_SEQ), np.int32)
    labels = np.zeros((len(items), LM_SEQ), np.int32)
    mask = np.zeros((len(items), LM_SEQ), np.int32)
    for i, it in enumerate(items):
        toks = np.asarray(_bpe_ish(it["text"])[:LM_SEQ], np.int32)
        n = len(toks)
        ids[i, :n] = toks
        labels[i, :n] = toks
        mask[i, :n] = 1
    return {"input_ids": ids, "labels": labels, "attention_mask": mask}


def build_lm_leg(on_tpu: bool):
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHead

    batch = 64
    if on_tpu:
        cfg_m = GPT2Config(vocab_size=LM_VOCAB, n_positions=128,
                           n_embd=768, n_layer=12, n_head=12)
    else:
        cfg_m = GPT2Config(vocab_size=LM_VOCAB, n_positions=128,
                           n_embd=16, n_layer=1, n_head=2)
    model = GPT2LMHead(cfg_m)
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": np.zeros((2, LM_SEQ), np.int32)})["params"]
    engine = _make_engine(model, params, batch)
    rng = np.random.default_rng(0)
    data = [{"text": _TEXT[:int(rng.integers(60, len(_TEXT)))]}
            for _ in range(2 * batch)]
    return engine, data, lm_collate, {"leg": "lm", "batch": batch,
                                      "seqlen": LM_SEQ}


def build_host_bound_leg(on_tpu: bool):
    """Feature-regression workload: collate+staging moves megabytes per step
    (C-level, GIL-free) while the model reduces them cheaply — the
    input-bandwidth-bound regime the prefetch pipeline targets."""
    import jax.numpy as jnp

    batch, seq, feat = 64, 128, 256

    def model(params, b):
        h = jnp.mean(b["x"], axis=1) @ params["w1"]
        pred = jnp.tanh(h) @ params["w2"]
        return jnp.mean((pred - b["y"]) ** 2)

    rng = np.random.default_rng(0)
    params = {"w1": rng.standard_normal((feat, 64)).astype(np.float32) * 0.05,
              "w2": rng.standard_normal((64, 16)).astype(np.float32) * 0.05}
    engine = _make_engine(model, params, batch)
    data = [{"x": rng.standard_normal((seq, feat)).astype(np.float32),
             "y": rng.standard_normal((16,)).astype(np.float32)}
            for _ in range(2 * batch)]
    return engine, data, None, {"leg": "host_bound", "batch": batch,
                                "item_bytes": seq * feat * 4}


def _make_engine(model, params, batch):
    import deepspeed_tpu
    cfg = {"train_batch_size": batch,
           "steps_per_print": 0,
           "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}}
    engine, *_ = deepspeed_tpu.initialize(model=model, model_parameters=params,
                                          config=cfg)
    return engine


# --------------------------------------------------------------------------- #
# offload legs (--offload): serial host step vs the fetch/step/upload pipeline
# --------------------------------------------------------------------------- #

def build_offload_leg(on_tpu: bool, smoke: bool, nvme_dir=None):
    """Param-heavy / flops-light workload: most leaves only feed a cheap
    mean-square regulariser, so their grads are full-size but the device
    step is a pass or two — the host optimizer is the step's centre of
    gravity, exactly the regime ZeRO-Offload targets."""
    import jax.numpy as jnp

    batch, feat, hidden = 16, 256, 64
    # full size: 4 x 2M-element wide leaves (8.4M params, ~34 MB fp32
    # masters) — large enough that the host kernel+upload dominate the step
    # (the ZeRO-Offload regime) and each group's kernel can hide its
    # neighbour's upload; smaller sizes drown the overlap in the device
    # step's fixed cost on a 2-core CPU box
    n_wide, wide = (4, 1 << 16) if smoke else (4, 1 << 21)

    def model(params, b):
        h = jnp.tanh(jnp.mean(b["x"], axis=1) @ params["w1"])
        pred = h @ params["w2"]
        loss = jnp.mean((pred - b["y"]) ** 2)
        reg = sum(jnp.mean(params[f"u{i}"] ** 2) for i in range(n_wide))
        return loss + 1e-4 * reg

    rng = np.random.default_rng(0)
    params = {"w1": rng.standard_normal((feat, hidden)).astype(np.float32) * .05,
              "w2": rng.standard_normal((hidden, 16)).astype(np.float32) * .05}
    for i in range(n_wide):
        params[f"u{i}"] = rng.standard_normal(wide).astype(np.float32) * .05

    import deepspeed_tpu
    off = {"device": "cpu", "buffer_count": 2}
    if nvme_dir is not None:
        off.update({"device": "nvme", "nvme_path": nvme_dir,
                    "pipeline_read": True, "pipeline_write": True})
    cfg = {"train_batch_size": batch, "steps_per_print": 0,
           "zero_optimization": {"stage": 1, "offload_optimizer": off},
           "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}}
    engine, *_ = deepspeed_tpu.initialize(model=model, model_parameters=params,
                                          config=cfg)
    batches = [{"x": rng.standard_normal((batch, 8, feat)).astype(np.float32),
                "y": rng.standard_normal((batch, 16)).astype(np.float32)}
               for _ in range(4)]
    n_params = sum(int(np.prod(v.shape)) for v in params.values())
    return engine, batches, {
        "leg": "offload_nvme" if nvme_dir else "offload_cpu",
        "batch": batch, "params": n_params,
        "host_groups": len(engine._offload_groups),
        "host_kernel": engine._offload.kernel.backend,
        "host_workers": engine._offload._workers}


def snapshot_offload(engine):
    import jax
    master, moments = engine._offload.state_leaves()
    host = ({k: np.array(v, np.float32) for k, v in master.items()},
            {sk: {k: np.array(v, np.float32) for k, v in d.items()}
             for sk, d in moments.items()},
            engine._offload.step_num)
    return (jax.device_get(engine.state), host, engine.global_steps,
            engine.global_samples, engine.micro_steps)


def restore_offload(engine, snap):
    import jax
    state, (master, moments, step_num), steps, samples, micro = snap
    engine.state = jax.device_put(state, engine._state_shardings)
    engine._offload.load_master_leaves(master)
    engine._offload.load_moment_leaves(moments, step_num=step_num)
    engine.global_steps = steps
    engine.global_samples = samples
    engine.micro_steps = micro
    engine._pending_metrics.clear()
    engine._last_metrics = {}


def offload_run(engine, batches, n: int, overlap: bool):
    """n steps through the SAME engine, host step orchestration selected by
    ``overlap_step`` (the device program and the kernel math are shared —
    only the overlap differs)."""
    engine._offload_cfg.overlap_step = overlap
    losses = []
    gc.disable()
    t0 = time.time()
    for i in range(n):
        losses.append(float(engine.train_batch(batches[i % len(batches)])))
    wall = time.time() - t0
    gc.enable()
    return losses, wall


def run_offload_leg(on_tpu: bool, steps: int, reps: int, smoke: bool,
                    nvme_dir=None):
    engine, batches, info = build_offload_leg(on_tpu, smoke, nvme_dir)
    snap = snapshot_offload(engine)
    warm = max(2, min(4, steps))
    for overlap in (False, True):   # warm both orchestrations + the merge jit
        offload_run(engine, batches, warm, overlap)
        restore_offload(engine, snap)

    c0 = engine.compiles
    speedups, sync_walls, pipe_walls = [], [], []
    equal, first_losses = True, None
    phase = {"steps": 0, "groups": 0, "fetch": 0.0, "kernel": 0.0,
             "upload": 0.0, "swap": 0.0, "depth": 0}
    for _ in range(reps):
        losses_s, wall_s = offload_run(engine, batches, steps, overlap=False)
        restore_offload(engine, snap)
        engine.offload_stats.reset()   # phase breakdown: pipelined runs only
        losses_p, wall_p = offload_run(engine, batches, steps, overlap=True)
        st = engine.offload_stats
        phase["steps"] += st.steps
        phase["groups"] += st.groups
        phase["fetch"] += st.fetch_ms
        phase["kernel"] += st.kernel_ms
        phase["upload"] += st.upload_ms
        phase["swap"] += st.swap_ms
        phase["depth"] += st.upload_depth_sum
        restore_offload(engine, snap)
        equal = equal and losses_p == losses_s
        if first_losses is None:
            first_losses = losses_s
        equal = equal and losses_s == first_losses
        speedups.append(wall_s / wall_p)
        sync_walls.append(wall_s)
        pipe_walls.append(wall_p)
    n = max(1, phase["steps"])
    g = max(1, phase["groups"])
    med = int(np.argsort(speedups)[len(speedups) // 2])
    out = dict(info)
    out.update({
        "steps": steps, "reps": reps,
        "sync_steps_per_sec": round(steps / sync_walls[med], 2),
        "pipelined_steps_per_sec": round(steps / pipe_walls[med], 2),
        "speedup": round(float(np.median(speedups)), 2),
        "speedup_reps": [round(float(s), 2) for s in speedups],
        "losses_equal": bool(equal),
        "compiles_during_timed_runs": engine.compiles - c0,
        "fetch_ms_per_group": round(phase["fetch"] / g, 3),
        "kernel_ms_per_group": round(phase["kernel"] / g, 3),
        "upload_ms_per_group": round(phase["upload"] / g, 3),
        "swap_ms_per_step": round(phase["swap"] / n, 3),
        "upload_depth_per_group": round(phase["depth"] / g, 3),
    })
    engine.destroy()
    del engine
    gc.collect()
    return out


# --------------------------------------------------------------------------- #
# preemption tolerance (--preempt): kill-and-resume onto a different device
# count (docs/ELASTICITY.md). Subprocess workers so a mid-step/mid-write KILL
# (os._exit via DSTPU_FAULTS) is a real process death: no atexit, no finally.
# --------------------------------------------------------------------------- #

# shared elastic schema: final global batch is world-size-INDEPENDENT, so a
# resume at M != N devices trains on the identical per-step global batch
PREEMPT_ELASTIC = {"enabled": True, "max_train_batch_size": 32,
                   "micro_batch_sizes": [4, 8], "min_gpus": 1, "max_gpus": 8,
                   "version": 0.2}
PREEMPT_FEAT, PREEMPT_SEQ, PREEMPT_OUT = 32, 4, 8
PREEMPT_EVERY = 3            # rolling cadence (steps)
PREEMPT_KILL_STEP = 8        # NOT a multiple of the cadence — a mid-run death
PREEMPT_TAG_PREFIX = "rolling_step"


def _preempt_batch(step: int, global_batch: int):
    """The step's global batch, keyed by step index ONLY — every world size
    and every resume sees byte-identical data for step k."""
    rng = np.random.default_rng(10_000 + step)
    return {"x": rng.standard_normal(
                (global_batch, PREEMPT_SEQ, PREEMPT_FEAT)).astype(np.float32),
            "y": rng.standard_normal(
                (global_batch, PREEMPT_OUT)).astype(np.float32)}


def preempt_worker(args):
    """One training run in THIS process: data-parallel over however many
    devices XLA_FLAGS forced, rolling checkpoints on a cadence, optional
    resume from a universal checkpoint (different-world path) or a regular
    tag (the verified-load control). Writes a JSON report to --out."""
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.checkpoint.universal import load_universal_into_engine
    from deepspeed_tpu.elasticity import compute_elastic_config
    from deepspeed_tpu.utils.compile_cache import setup_compile_cache

    setup_compile_cache()
    world = jax.device_count()
    final_batch, _valid, micro = compute_elastic_config(
        {"elasticity": PREEMPT_ELASTIC}, world_size=world,
        return_microbatch=True)
    gas = final_batch // (micro * world)

    import jax.numpy as jnp

    def model(params, b):
        h = jnp.tanh(jnp.mean(b["x"], axis=1) @ params["w1"])
        pred = h @ params["w2"]
        return jnp.mean((pred - b["y"]) ** 2)

    rng = np.random.default_rng(0)
    params = {"w1": rng.standard_normal(
                  (PREEMPT_FEAT, 16)).astype(np.float32) * 0.05,
              "w2": rng.standard_normal(
                  (16, PREEMPT_OUT)).astype(np.float32) * 0.05}
    cfg = {"train_batch_size": final_batch,
           "train_micro_batch_size_per_gpu": micro,
           "mesh": {"data": -1}, "steps_per_print": 0,
           "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
           "checkpoint": {"engine": "async", "writers": 2,
                          "verify_load": True,
                          "rolling": {"every_n_steps": PREEMPT_EVERY,
                                      "save_dir": args.save_dir,
                                      "keep_last": 8, "max_pending": 2}}}
    engine, *_ = deepspeed_tpu.initialize(model=model, model_parameters=params,
                                          config=cfg)
    resume_tag = None
    if args.resume_universal:
        load_universal_into_engine(engine, args.resume_universal)
        resume_tag = "universal"
    elif args.resume_tag:
        engine.load_checkpoint(args.load_dir, tag=args.resume_tag, verify=True)
        resume_tag = args.resume_tag
    start_step = engine.global_steps

    losses = {}
    compiles_warm = None
    for step in range(start_step, args.total_steps):
        loss = float(engine.train_batch(_preempt_batch(step, final_batch)))
        losses[str(step + 1)] = loss
        if step == start_step:
            # the first (re)started step pays the (re)compile; everything
            # after must hit the executable cache — the zero-recompile gate
            compiles_warm = engine.compiles
    out = {"world": world, "micro": micro, "gas": gas,
           "global_batch": final_batch, "start_step": start_step,
           "resume_tag": resume_tag, "losses": losses,
           "compiles_after_warmup":
               (engine.compiles - compiles_warm)
               if compiles_warm is not None else 0,
           "ckpt_saves": engine.ckpt_stats.saves}
    engine.destroy()   # flushes rolling commits + closes the async writers
    with open(args.out, "w") as f:
        json.dump(out, f)


def _spawn_preempt_worker(devices: int, total_steps: int, save_dir: str,
                          out_path: str, faults: str = "",
                          resume_universal: str = "", load_dir: str = "",
                          resume_tag: str = ""):
    import subprocess
    env = dict(os.environ)
    env.pop("DSTPU_FAULTS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    if faults:
        env["DSTPU_FAULTS"] = faults
    cmd = [sys.executable, os.path.abspath(__file__), "--preempt-worker",
           "--devices", str(devices), "--total-steps", str(total_steps),
           "--save-dir", save_dir, "--out", out_path]
    if resume_universal:
        cmd += ["--resume-universal", resume_universal]
    if resume_tag:
        cmd += ["--load-dir", load_dir, "--resume-tag", resume_tag]
    return subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=600)


def _read_report(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def run_preempt_leg(total_steps: int) -> bool:
    """Kill at a non-checkpoint step AND mid-checkpoint-write; resume each
    onto a DIFFERENT simulated device count; gate byte-identical loss streams
    (resumed vs an uninterrupted verified-load run from the same surviving
    checkpoint), the global-batch invariant, zero post-warmup recompiles, and
    loss-curve continuity vs the uninterrupted original-world run."""
    import tempfile
    from deepspeed_tpu.checkpoint.state import find_resume_tag, tag_problem
    from deepspeed_tpu.checkpoint.universal import ds_to_universal
    from deepspeed_tpu.utils.fault_injection import KILL_EXIT_CODE

    N, M = 4, 2
    ok = True
    with tempfile.TemporaryDirectory() as td:
        # uninterrupted reference at the ORIGINAL world size (also proves a
        # full rolling run commits every cadence point and prunes cleanly)
        ref_out = os.path.join(td, "ref.json")
        p = _spawn_preempt_worker(N, total_steps, os.path.join(td, "ref"),
                                  ref_out)
        if p.returncode != 0:
            print(json.dumps({"leg": "preempt", "error": "ref run failed",
                              "stderr": p.stderr[-2000:]}), flush=True)
            return False
        ref = _read_report(ref_out)

        # the second spec on step.kill stalls EVERY step 250 ms (the kill spec
        # is listed first, so the kill still wins at its hit): on this box the
        # tiny steps outrun the background committer, and a kill landing
        # before the previous cadence tag committed would leave nothing to
        # resume from — which is a valid preemption outcome, but not the one
        # these legs exist to gate. Real steps are >> commit latency.
        pace = "step.kill:every=1:action=stall:delay_s=0.25"
        legs = {
            # dies between steps: the surviving checkpoint is a committed
            # cadence tag strictly older than the kill step
            "kill_step":
                f"step.kill:at={PREEMPT_KILL_STEP}:action=kill;{pace}",
            # dies INSIDE a rolling tag's npz write (hit 3 = the second
            # cadence save's first file): that tag must be detected as torn
            # and resume must fall back to the previous complete tag
            "kill_write": f"ckpt.writer:at=3:action=kill;{pace}",
        }
        for name, plan in legs.items():
            save_dir = os.path.join(td, name)
            res = {"leg": f"preempt_{name}", "orig_world": N,
                   "resume_world": M}
            p = _spawn_preempt_worker(N, total_steps, save_dir,
                                      os.path.join(td, f"{name}_a.json"),
                                      faults=plan)
            res["killed_with_injection_exit"] = p.returncode == KILL_EXIT_CODE
            tag = find_resume_tag(save_dir)
            res["resume_tag"] = tag
            surviving_ok = (
                tag is not None and tag.startswith(PREEMPT_TAG_PREFIX)
                and tag_problem(save_dir, tag) is None)
            k = int(tag[len(PREEMPT_TAG_PREFIX):]) if surviving_ok else -1
            res["resume_step"] = k
            surviving_ok = surviving_ok and 0 < k < PREEMPT_KILL_STEP \
                and k % PREEMPT_EVERY == 0
            if name == "kill_write":
                # the torn tag is still on disk — and is NOT the one chosen
                torn = os.path.join(save_dir,
                                    f"{PREEMPT_TAG_PREFIX}{2 * PREEMPT_EVERY}")
                res["torn_tag_present"] = os.path.isdir(torn)
                res["torn_tag_detected"] = tag_problem(
                    save_dir, os.path.basename(torn)) is not None
                surviving_ok = surviving_ok and res["torn_tag_present"] \
                    and res["torn_tag_detected"] \
                    and k == PREEMPT_EVERY
            res["surviving_checkpoint_ok"] = bool(surviving_ok)
            if not surviving_ok:
                res["stderr"] = p.stderr[-2000:]
                print(json.dumps(res), flush=True)
                ok = False
                continue

            # elastic resume: N-device checkpoint -> universal -> M devices
            uni = ds_to_universal(save_dir, os.path.join(td, f"{name}_uni"),
                                  tag=tag)
            rb_out = os.path.join(td, f"{name}_b.json")
            rc_out = os.path.join(td, f"{name}_c.json")
            pb = _spawn_preempt_worker(M, total_steps,
                                       os.path.join(td, f"{name}_b_ckpt"),
                                       rb_out, resume_universal=uni)
            pc = _spawn_preempt_worker(M, total_steps,
                                       os.path.join(td, f"{name}_c_ckpt"),
                                       rc_out, load_dir=save_dir,
                                       resume_tag=tag)
            if pb.returncode != 0 or pc.returncode != 0:
                res["error"] = "resume run failed"
                res["stderr"] = (pb.stderr + pc.stderr)[-2000:]
                print(json.dumps(res), flush=True)
                ok = False
                continue
            b, c = _read_report(rb_out), _read_report(rc_out)
            res["resumed_start_step"] = b["start_step"]
            res["resumed_world"] = b["world"]
            # the gates
            res["global_batch_invariant"] = (
                b["global_batch"] == ref["global_batch"]
                and b["world"] == M and ref["world"] == N)
            res["resumed_from_surviving_step"] = b["start_step"] == k \
                and c["start_step"] == k
            res["losses_byte_identical"] = b["losses"] == c["losses"] \
                and len(b["losses"]) == total_steps - k
            res["compiles_after_resume_warmup"] = (
                b["compiles_after_warmup"] + c["compiles_after_warmup"])
            ref_tail = [ref["losses"][s] for s in sorted(b["losses"], key=int)]
            got_tail = [b["losses"][s] for s in sorted(b["losses"], key=int)]
            # across device counts reduction order differs in the last bits;
            # byte-equality holds at fixed world (above), continuity here
            res["loss_continuity_vs_original_world"] = bool(
                np.allclose(got_tail, ref_tail, rtol=5e-4, atol=1e-6))
            leg_ok = (res["killed_with_injection_exit"]
                      and res["global_batch_invariant"]
                      and res["resumed_from_surviving_step"]
                      and res["losses_byte_identical"]
                      and res["compiles_after_resume_warmup"] == 0
                      and res["loss_continuity_vs_original_world"])
            res["ok"] = bool(leg_ok)
            print(json.dumps(res), flush=True)
            ok = ok and leg_ok
    return ok


def snapshot(engine):
    import jax
    return (jax.device_get(engine.state), engine.global_steps,
            engine.global_samples, engine.micro_steps)


def restore(engine, snap):
    import jax
    state, steps, samples, micro = snap
    engine.state = jax.device_put(state, engine._state_shardings)
    engine.global_steps = steps
    engine.global_samples = samples
    engine.micro_steps = micro
    engine._pending_metrics.clear()
    engine._last_metrics = {}


def fresh_iter(engine, dataset, collate):
    """A deterministic loader — every run builds its own so all runs see the
    identical batch order (same seed, epoch 0)."""
    from deepspeed_tpu.runtime.dataloader import RepeatingLoader
    return RepeatingLoader(engine.deepspeed_io(dataset, collate_fn=collate,
                                               shuffle=True))


def sync_run(engine, dataset, collate, n: int):
    """Pre-PR loop: per step, item-by-item collate, inline staging, and an
    immediate blocking ``float(loss)`` — the per-step host sync the deferred
    drain removed."""
    it = iter(fresh_iter(engine, dataset, collate))
    losses = []
    gc.disable()
    t0 = time.time()
    for _ in range(n):
        losses.append(float(engine.train_batch(next(it))))
    wall = time.time() - t0
    gc.enable()
    return losses, wall


def pipe_run(engine, dataset, collate, n: int, prefetch: int):
    """The async loop: producer-thread staging + multi-step dispatch with the
    one-step-late metric drain; losses materialise once at the end."""
    from deepspeed_tpu.runtime.data_pipeline import PrefetchLoader
    pl = PrefetchLoader(fresh_iter(engine, dataset, collate),
                        prepare=engine._prepare_batch, prefetch=prefetch,
                        start_step=engine.global_steps)
    try:
        gc.disable()
        t0 = time.time()
        losses = engine.train_steps(n, data_iter=iter(pl))
        wall = time.time() - t0
        gc.enable()
    finally:
        pl.close()
    return [float(x) for x in losses], wall


def run_leg(builder, on_tpu: bool, steps: int, reps: int, prefetch: int):
    engine, dataset, collate, info = builder(on_tpu)
    snap = snapshot(engine)
    warm = max(2, min(4, steps))

    # warmup: compile the fused step + warm both orchestration paths, then
    # rewind the engine so every timed run starts from identical parameters
    sync_run(engine, dataset, collate, warm)
    restore(engine, snap)
    pipe_run(engine, dataset, collate, warm, prefetch)
    restore(engine, snap)

    c0 = engine.compiles
    speedups, sync_walls, pipe_walls = [], [], []
    equal = True
    first_losses = None
    acc = {"steps": 0, "wait": 0.0, "build": 0.0, "dispatch": 0.0,
           "drain": 0.0, "prefetched": 0}
    for _ in range(reps):
        losses_s, wall_s = sync_run(engine, dataset, collate, steps)
        restore(engine, snap)
        engine.train_stats.reset()   # phase breakdown: pipelined steps only
        losses_p, wall_p = pipe_run(engine, dataset, collate, steps, prefetch)
        st = engine.train_stats
        acc["steps"] += st.steps
        acc["wait"] += st.enqueue_wait_ms
        acc["build"] += st.host_build_ms
        acc["dispatch"] += st.dispatch_ms
        acc["drain"] += st.drain_ms
        acc["prefetched"] += st.prefetched_steps
        restore(engine, snap)
        equal = equal and losses_p == losses_s
        if first_losses is None:
            first_losses = losses_s
        # restored state + same loader seed => every rep must replay the
        # exact same stream; drift here means the restore (or staging) leaks
        equal = equal and losses_s == first_losses
        speedups.append(wall_s / wall_p)
        sync_walls.append(wall_s)
        pipe_walls.append(wall_p)
    n = max(1, acc["steps"])
    out = dict(info)
    med = int(np.argsort(speedups)[len(speedups) // 2])
    out.update({
        "steps": steps,
        "reps": reps,
        "prefetch": prefetch,
        "sync_steps_per_sec": round(steps / sync_walls[med], 2),
        "pipelined_steps_per_sec": round(steps / pipe_walls[med], 2),
        "speedup": round(float(np.median(speedups)), 2),
        "speedup_reps": [round(float(s), 2) for s in speedups],
        # the tentpole gate: identical math, different orchestration
        "losses_equal": bool(equal),
        "compiles_during_timed_runs": engine.compiles - c0,
        "enqueue_wait_ms_per_step": round(acc["wait"] / n, 3),
        "host_build_ms_per_step": round(acc["build"] / n, 3),
        "dispatch_ms_per_step": round(acc["dispatch"] / n, 3),
        "drain_ms_per_step": round(acc["drain"] / n, 3),
        "prefetched_fraction": round(acc["prefetched"] / n, 3),
    })
    engine.destroy()
    del engine
    gc.collect()   # drop this leg's device state before the next leg times
    return out


def run_trace_overhead_leg(on_tpu: bool, steps: int, reps: int, smoke: bool):
    """Tracer-overhead gate (ISSUE 7 / BENCH_r10): the SAME pipelined
    host-bound loop with span tracing OFF vs ON, reps interleaved so slow
    drift on this shared box hits both sides equally. Tracing ON must leave
    the loss stream byte-identical, add zero compiles, and cost <= 5% wall
    (the ring-record path: perf_counter pairs + one tuple store per span —
    export is NOT on the timed path). Smoke mode keeps the correctness gates
    but loosens the overhead bar (8 steps x 1 rep on 2 shared cores is
    noise, not signal)."""
    from deepspeed_tpu.monitor.trace import tracer
    engine, dataset, collate, info = build_host_bound_leg(on_tpu)
    snap = snapshot(engine)
    was_enabled = tracer.enabled   # $DSTPU_TRACE may have armed it
    warm = max(2, min(4, steps))
    tracer.enabled = False
    pipe_run(engine, dataset, collate, warm, prefetch=2)
    restore(engine, snap)
    tracer.configure(enabled=True)
    pipe_run(engine, dataset, collate, warm, prefetch=2)
    restore(engine, snap)

    c0 = engine.compiles
    off_walls, on_walls = [], []
    equal = True
    first = None
    for rep in range(reps):
        # alternate which side runs first: slow drift on this shared box
        # (allocator state, thread scheduling) hits both sides equally
        walls = {}
        for trace_on in ((False, True) if rep % 2 == 0 else (True, False)):
            tracer.enabled = bool(trace_on)
            losses, wall = pipe_run(engine, dataset, collate, steps, 2)
            restore(engine, snap)
            walls[trace_on] = wall
            if first is None:
                first = losses
            equal = equal and losses == first
        tracer.enabled = False
        off_walls.append(walls[False])
        on_walls.append(walls[True])
    # per-rep ratios, then the median: one GC'd or descheduled run perturbs
    # one ratio, not the whole estimate
    ratios = [on / off for on, off in zip(on_walls, off_walls)]
    overhead = float(np.median(ratios)) - 1.0
    spans = sum(c for c, _ in tracer.summary().values())
    tracer.enabled = was_enabled
    bar = 0.25 if smoke else 0.05
    out = dict(info)
    out.update({
        "leg": "trace_overhead",
        "steps": steps,
        "reps": reps,
        "traceoff_steps_per_sec": round(steps / float(np.median(off_walls)), 2),
        "traceon_steps_per_sec": round(steps / float(np.median(on_walls)), 2),
        "overhead_frac": round(overhead, 4),
        "overhead_frac_reps": [round(r - 1.0, 4) for r in ratios],
        "overhead_bar": bar,
        "spans_recorded": spans,
        "losses_equal": bool(equal),
        "compiles_during_timed_runs": engine.compiles - c0,
    })
    out["ok"] = bool(equal and out["compiles_during_timed_runs"] == 0
                     and overhead <= bar and spans > 0)
    engine.destroy()
    del engine
    gc.collect()
    return out


def run_zero3_overlap_leg(on_tpu: bool, steps: int, reps: int, smoke: bool):
    """ZeRO-3 collective-schedule leg (docs/TRAINING.md "ZeRO-3 collective
    schedule"): a param-heavy GPT2 stack sharded over an 8-way fsdp mesh,
    driven at stage3_prefetch_depth 0 (serial gather-then-compute baseline)
    vs 1 and 2 (pipelined prefetch + reduce-scatter under backward).

    Gates: per-step loss streams BYTE-IDENTICAL across all scheduled depths
    (the schedule moves collectives, never math); zero compiles during the
    timed runs. The implicit (XLA-scheduled) path is compared to fp32
    tolerance only — its combiner reduces grads in a different order (~1 ulp
    drift). How much collective time the schedule hides is device work and is
    read from a device trace (the waves carry ``zero3/gather/w<k>`` scopes;
    ``chipbench`` reports ``collective_hidden_share``), not measured here.

    The steps/sec ratio is REPORTED against a 1.15x bar but only GATED on a
    real accelerator: a forced-host CPU mesh executes thunks serially, so
    scheduled overlap cannot convert to wall-clock there."""
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHead

    batch, seq = 8, 32
    n_embd, n_layer = (64, 4) if smoke else (192, 6)
    cfg_m = GPT2Config(vocab_size=LM_VOCAB, n_positions=seq,
                       n_embd=n_embd, n_layer=n_layer, n_head=4)
    rng = np.random.default_rng(0)
    batches = [{"input_ids": rng.integers(0, LM_VOCAB, size=(batch, seq))
                .astype(np.int32)} for _ in range(4)]

    def build(depth):
        model = GPT2LMHead(cfg_m)
        params = model.init(jax.random.PRNGKey(0), batches[0])["params"]
        z = {"stage": 3, "stage3_param_persistence_threshold": 0}
        if depth is not None:
            # bucket sized to roughly one transformer layer so the stack
            # packs into one wave per layer — multiple waves is what gives
            # the prefetch something to pipeline
            bucket = (1 << 18) if smoke else (1 << 21)
            z.update({"stage3_prefetch_depth": depth,
                      "allgather_bucket_size": bucket,
                      "reduce_bucket_size": bucket})
        engine, *_ = deepspeed_tpu.initialize(
            model=model, model_parameters=params,
            config={"train_batch_size": batch, "steps_per_print": 0,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                    "zero_optimization": z, "mesh": {"fsdp": 8}})
        return engine

    def run(engine, n, start):
        losses = []
        gc.disable()
        t0 = time.time()
        for i in range(n):
            losses.append(float(engine.train_batch(
                batches[(start + i) % len(batches)])))
        wall = time.time() - t0
        gc.enable()
        return losses, wall

    streams, rates, out = {}, {}, {}
    compiles_during_timed = 0
    for depth in (0, 1, 2):
        engine = build(depth)
        assert engine._zero3_plan is not None, "zero3 schedule did not arm"
        losses, _ = run(engine, steps, start=0)        # includes compiles
        streams[depth] = [np.float32(x).tobytes() for x in losses]
        c0 = engine.compiles
        walls = []
        for r in range(reps):
            _, wall = run(engine, steps, start=(1 + r) * steps)
            walls.append(wall)
        engine.drain_metrics()
        compiles_during_timed += engine.compiles - c0
        rates[depth] = steps / float(np.median(walls))
        if depth == 0:
            out["waves_per_step"] = engine._zero3_plan.n_waves
            out["gather_mb_per_step"] = round(
                engine._zero3_plan.gather_bytes_per_step / 1e6, 2)
        engine.destroy()
        del engine
        gc.collect()

    implicit = build(None)
    assert implicit._zero3_plan is None
    imp_losses, _ = run(implicit, steps, start=0)
    implicit.destroy()
    del implicit
    gc.collect()
    base = [np.frombuffer(b, np.float32)[0] for b in streams[0]]
    byte_equal = streams[0] == streams[1] == streams[2]
    implicit_close = bool(np.allclose(imp_losses, base, rtol=1e-5))
    speedup = rates[2] / rates[0] if rates[0] > 0 else 0.0
    bar = 1.15
    out.update({
        "leg": "zero3_overlap",
        "steps": steps, "reps": reps, "devices": len(jax.devices()),
        "model": {"n_embd": n_embd, "n_layer": n_layer, "seq": seq},
        "losses_equal": bool(byte_equal),
        "implicit_allclose": implicit_close,
        "compiles_during_timed_runs": compiles_during_timed,
        "steps_per_sec": {f"depth{d}": round(r, 3)
                          for d, r in rates.items()},
        "speedup_d2_vs_d0": round(speedup, 3),
        "speedup_bar": bar,
        "wall_clock_meaningful": bool(on_tpu),
    })
    if not on_tpu:
        out["caveat"] = (
            "forced-host CPU mesh: XLA:CPU executes thunks serially, so the "
            "scheduled overlap cannot convert to wall-clock; the 1.15x bar "
            "applies on hardware with async collectives")
    out["ok"] = bool(byte_equal and implicit_close
                     and compiles_during_timed == 0
                     and (speedup >= bar or not on_tpu))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--prefetch", type=int, default=2)
    # host_bound (the acceptance-gate leg) runs first so its numbers are not
    # skewed by allocator/thread-pool state the lm leg leaves behind
    ap.add_argument("--legs", default="host_bound,lm")
    ap.add_argument("--offload", action="store_true",
                    help="run the offloaded-optimizer legs "
                         "(offload_cpu,offload_nvme) instead of --legs")
    ap.add_argument("--preempt", action="store_true",
                    help="kill-and-resume leg (docs/ELASTICITY.md): kill a "
                         "subprocess run mid-step and mid-checkpoint-write, "
                         "resume on a different simulated device count, gate "
                         "byte-identical loss continuation")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fast run for CI (scripts/bench_smoke.sh): "
                         "correctness gates only, throughput is noise")
    ap.add_argument("--trace-overhead", action="store_true",
                    help="span-tracer overhead leg (docs/OBSERVABILITY.md): "
                         "pipelined host-bound loop trace-off vs trace-on, "
                         "gating byte-identical losses, zero compiles, and "
                         "<=5%% overhead (BENCH_r10)")
    ap.add_argument("--zero3-overlap", action="store_true",
                    help="ZeRO-3 collective-schedule leg (docs/TRAINING.md): "
                         "prefetch depth 0 vs 1/2 over an 8-way fsdp mesh, "
                         "gating byte-identical loss streams, zero timed "
                         "compiles, and span-measured gather/compute overlap")
    # internal: one subprocess training run of the --preempt harness
    ap.add_argument("--preempt-worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--devices", type=int, default=0, help=argparse.SUPPRESS)
    ap.add_argument("--total-steps", type=int, default=12,
                    help=argparse.SUPPRESS)
    ap.add_argument("--save-dir", default="", help=argparse.SUPPRESS)
    ap.add_argument("--out", default="", help=argparse.SUPPRESS)
    ap.add_argument("--resume-universal", default="", help=argparse.SUPPRESS)
    ap.add_argument("--load-dir", default="", help=argparse.SUPPRESS)
    ap.add_argument("--resume-tag", default="", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.preempt_worker:
        preempt_worker(args)
        return
    if args.preempt:
        # 12 steps: cadence saves at 3/6/9/12, kill at 8 — small enough for
        # the CI smoke budget, large enough that every gate has teeth
        sys.exit(0 if run_preempt_leg(total_steps=12) else 1)
    if args.smoke:
        args.steps, args.reps = 8, 1
    if args.offload:
        args.legs = "offload_cpu,offload_nvme"
    if args.zero3_overlap:
        # the leg needs an 8-way fsdp mesh; on a CPU host force 8 virtual
        # devices BEFORE jax initialises (same discipline as tests/conftest)
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()

    import jax
    on_tpu = jax.default_backend() not in ("cpu",)
    from deepspeed_tpu.utils.compile_cache import setup_compile_cache
    setup_compile_cache()
    if args.trace_overhead:
        # even in smoke mode the ratio needs a few interleaved reps — a
        # single 8-step pair on 2 shared cores measures the scheduler
        reps = max(3, args.reps) if args.smoke else max(5, args.reps)
        out = run_trace_overhead_leg(on_tpu, args.steps, reps, args.smoke)
        print(json.dumps(out), flush=True)
        sys.exit(0 if out["ok"] else 1)
    if args.zero3_overlap:
        out = run_zero3_overlap_leg(on_tpu, args.steps, args.reps, args.smoke)
        print(json.dumps(out), flush=True)
        sys.exit(0 if out["ok"] else 1)
    builders = {"lm": build_lm_leg, "host_bound": build_host_bound_leg}
    offload_legs = ("offload_cpu", "offload_nvme")
    bad = [l for l in args.legs.split(",")
           if l not in builders and l not in offload_legs]
    if bad:
        ap.error(f"unknown --legs entries {bad}; valid: "
                 f"{sorted(builders) + list(offload_legs)}")
    ok = True
    offload_outs = {}
    for leg in args.legs.split(","):
        if leg in offload_legs:
            if leg == "offload_nvme":
                import tempfile
                with tempfile.TemporaryDirectory() as nvme_dir:
                    out = run_offload_leg(on_tpu, args.steps, args.reps,
                                          args.smoke, nvme_dir=nvme_dir)
            else:
                out = run_offload_leg(on_tpu, args.steps, args.reps,
                                      args.smoke)
            offload_outs[leg] = out
        else:
            out = run_leg(builders[leg], on_tpu, args.steps, args.reps,
                          args.prefetch)
        print(json.dumps(out), flush=True)
        # gates: pipelined orchestration must not change the loss stream and
        # warm steady-state training must never compile — a staging or
        # bucket-cache regression shows up here before it becomes a
        # throughput mystery
        ok = ok and out["losses_equal"] \
            and out["compiles_during_timed_runs"] == 0
    if "offload_cpu" in offload_outs and "offload_nvme" in offload_outs:
        # the nvme tier's honest bound: no slower than the cpu tier by more
        # than the pure IO cost it actually paid (swap waits per step)
        cpu, nvme = offload_outs["offload_cpu"], offload_outs["offload_nvme"]
        cpu_step_ms = 1e3 / max(cpu["pipelined_steps_per_sec"], 1e-9)
        nvme_step_ms = 1e3 / max(nvme["pipelined_steps_per_sec"], 1e-9)
        io_ms = nvme["swap_ms_per_step"]
        # 1.5x slack on the measured IO: this box is 2 shared cores
        within = bool(
            nvme_step_ms <= cpu_step_ms + 1.5 * io_ms + 0.25 * cpu_step_ms)
        print(json.dumps({
            "leg": "offload_nvme_vs_cpu",
            "cpu_step_ms": round(cpu_step_ms, 3),
            "nvme_step_ms": round(nvme_step_ms, 3),
            "nvme_io_ms_per_step": round(io_ms, 3),
            "within_io_cost": within,
        }), flush=True)
        ok = ok and within
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
