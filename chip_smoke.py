"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py                # one TPU chip: kernels, train, serve
    python chip_smoke.py --multichip    # four chips: ZeRO-3 sharded training

Drives the two main paths through the entry points a user calls —
``deepspeed_tpu.initialize(...).train_batch`` and ``InferenceEngineV2`` behind
``ServingFrontend`` — at the full width of Mistral-7B
(``LlamaConfig.mistral_7b``: hidden 4096, 32 q / 8 kv heads, head_dim 128,
FFN 14336, vocab 32000, window 4096). Widths are never cut. Depth is, to what
one 16 GB chip holds, and the cut is printed. Weights are random, made from
``--seed``.

One process, no child that touches JAX: a chip belongs to one process. No
phase is wrapped in a catch that lets the run go on; any exception, any failed
check, or a platform other than ``tpu`` ends the run with a non-zero exit code
and no result line. On success the LAST line of standard output is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and everything else (versions, cache directory and entries, per-phase wall and
compile seconds, depths, peak HBM) is printed on earlier lines. Any rate
printed here is a smoke figure from a handful of steps, not a measurement.

The phases take their sizes from a :class:`Sizes`. ``main`` runs
:data:`REAL`; ``scripts/chip_rehearse.py`` runs the same phases on the CPU at a
small one and compiles the real one for a described chip, so that a call to
the chip is not spent on finding a wrong argument.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Tuple


# --------------------------------------------------------------------------- #
# sizes
# --------------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the phases size themselves by."""
    #: overrides on ``LlamaConfig.mistral_7b`` — empty at the published widths
    widths: Dict[str, Any]
    seq: int                 # training sequence length (<= window: flash path)
    train_layers: int        # one chip, ~16 B/parameter of state
    serve_layers: int        # bf16 weights + KV pages on one chip
    block_size: int          # KV page size in tokens
    max_context: int         # serving: > window, so the page ring is live
    prompts: Tuple[int, ...]           # serving request prompt lengths
    new_tokens: Tuple[int, ...]        # ... and tokens to generate
    ref_prompt: int          # logits check: prompt length (jnp-attention range)
    multi_layers: int        # --multichip: depth over four chips
    multi_ref_layers: int    # --multichip: depth that also fits one chip


REAL = Sizes(
    widths={},
    seq=4096,
    train_layers=2,
    serve_layers=16,
    block_size=128,
    max_context=5120,
    prompts=(256, 512, 777, 1024, 1500, 2048, 3000, 4000),
    # the last stream ends at 4000 + 200 tokens: past the 4096-token window
    new_tokens=(32, 32, 32, 32, 32, 32, 32, 200),
    ref_prompt=900,
    multi_layers=8,
    multi_ref_layers=2,
)

PUBLISHED_LAYERS = 32        # LlamaConfig.mistral_7b().num_hidden_layers
TRAIN_STEPS = 4              # after one warm-up step
FORCED_TOKENS = 4            # decode steps in the logits check
HBM_FILL = 0.90              # serving: weights + pages as a share of HBM
HBM_HEADROOM = 1 << 30       # ... less this, for activations and logits

# Tolerances, each with its reason.
#
# Kernels, max |kernel - reference| over max |reference|. The kernels keep
# scores and the running softmax in f32 but round p to bf16 for the p@V dot
# and round the output to bf16 (2^-9 relative each); the jnp references keep
# f32 to the end. These are the bounds the repo's last on-chip kernel grid
# used (bench.py r05: 2e-2, and 3e-2 for int8 pages against attention over
# the dequantized pages).
TOL_KERNEL = 2e-2
TOL_KERNEL_INT8 = 3e-2
# Serving logits against the model's own dense f32 forward, max |diff| over
# max |reference logit|. The engine runs bf16 activations (2^-8 relative per
# rounding) through `serve_layers` residual layers of two roundings each; as
# a random walk that is sqrt(2 * 16) * 2^-8 ~ 2.2e-2 of the signal, and the
# bound leaves a factor of two over it.
TOL_LOGITS = 5e-2
# Training loss streams of the same seed and global batch on 1 and 4 chips
# (--multichip), absolute, on losses near ln(32000) = 10.4: the runs differ
# in reduction order only (per-chip microbatches vs accumulation, XLA's
# resharding vs the explicit schedule), which in bf16 compute moves a loss by
# ~1e-3 relative; the bound is 0.5% of the loss.
TOL_LOSS = 5e-2


def log(msg: str) -> None:
    print(f"[smoke +{time.time() - _T0:7.1f}s] {msg}", flush=True)


_T0 = time.time()


def check(ok: bool, what: str) -> None:
    """A failed check ends the run (SystemExit is not caught anywhere)."""
    if not ok:
        print(f"SMOKE CHECK FAILED: {what}", flush=True)
        raise SystemExit(1)


def model_config(sizes: Sizes, layers: int, **kw):
    from deepspeed_tpu.models.llama import LlamaConfig
    return LlamaConfig.mistral_7b(num_hidden_layers=layers,
                                  **{**sizes.widths, **kw})


def train_config(global_batch: int, fsdp: int = 1,
                 prefetch_depth: Optional[int] = None) -> dict:
    """ZeRO stage 3 spelled out (at fsdp=1 the sharding is degenerate, but
    the step is the one the four-chip path runs), bf16, AdamW."""
    zero = {"stage": 3, "stage3_param_persistence_threshold": 0}
    if prefetch_depth is not None:
        zero["stage3_prefetch_depth"] = prefetch_depth
    return {
        "train_batch_size": global_batch,
        "train_micro_batch_size_per_gpu": 1,
        "steps_per_print": 0,
        # one repeated batch is memorised within a few steps; at 3e-4 the
        # loss overshot on its fourth step on the chip (10.9 7.9 3.8 0.8 3.7)
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": zero,
        "mesh": {"data": 1, "fsdp": fsdp},
    }


def serve_config(sizes: Sizes, num_blocks: int) -> dict:
    import jax.numpy as jnp
    slots, chunk = 4, 2 * sizes.block_size
    rows = len(sizes.prompts)
    return {
        "dtype": jnp.bfloat16,
        "state_manager": {"max_tracked_sequences": 2 * rows,
                          "max_ragged_sequence_count": rows,
                          "max_ragged_batch_size": rows + slots * chunk,
                          "max_context": sizes.max_context,
                          "prefill_chunk_size": chunk},
        "kv_cache": {"block_size": sizes.block_size,
                     "num_blocks": num_blocks},
        "compile": {"warmup": True},
        # a windowed model's page ring cannot be preempted (frontend.py);
        # one class whose SLOs a cold smoke cannot miss, so nothing is shed
        "serving": {"preemption": "none",
                    "classes": [{"name": "smoke", "priority": 1,
                                 "ttft_slo_ms": 6e5, "tbt_slo_ms": 6e5}]},
    }


# --------------------------------------------------------------------------- #
# bookkeeping: compile seconds, cache entries, device memory
# --------------------------------------------------------------------------- #

class CompileClock:
    """Seconds this process spent in backend compiles (a persistent-cache
    hit counts its retrieval), and the cache's hits and misses."""

    def __init__(self):
        from jax import monitoring
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def cache_entries(directory: str) -> int:
    """Executables in the cache (jax's ``-atime`` bookkeeping files are
    touched on hits and are not entries)."""
    return sum(1 for _, _, files in os.walk(directory)
               for f in files if not f.endswith("-atime"))


def memory_line(devices) -> str:
    parts = []
    for d in devices:
        st = d.memory_stats()
        if st is None:            # the CPU backend (rehearsal) reports none
            return "no device memory statistics on this backend"
        parts.append(f"dev{d.id} in_use {st['bytes_in_use'] / 2**30:.2f} GiB "
                     f"peak {st['peak_bytes_in_use'] / 2**30:.2f} GiB "
                     f"(of {st['bytes_limit'] / 2**30:.2f})")
    return "; ".join(parts)


def run_phase(name: str, fn: Callable[[], Any], clock: CompileClock,
              devices) -> Any:
    """Run one phase and print what it cost. No catch: a phase that raises
    ends the run."""
    log(f"--- phase {name} ---")
    t0, c0, h0, m0 = time.time(), clock.seconds, clock.hits, clock.misses
    out = fn()
    log(f"phase {name}: wall {time.time() - t0:.1f} s, compile "
        f"{clock.seconds - c0:.1f} s (cache hits {clock.hits - h0}, misses "
        f"{clock.misses - m0}); {memory_line(devices)}")
    return out


# --------------------------------------------------------------------------- #
# phase: kernels against the references beside them
# --------------------------------------------------------------------------- #

def rel_err(got, ref) -> float:
    import jax.numpy as jnp
    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    return float(jnp.max(jnp.abs(got - ref)) / (jnp.max(jnp.abs(ref)) + 1e-9))


def phase_kernels(sizes: Sizes, seed: int, on_chip: bool = True) -> None:
    """The compiled main-path kernels at the smoke model's shapes against the
    ``*_reference`` functions beside them. ``on_chip=False`` (CPU rehearsal)
    drops only the assertion that a Mosaic kernel is in the program."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.ops.attention import reference_attention
    from deepspeed_tpu.ops.pallas import paged_attention as pa
    from deepspeed_tpu.ops.pallas import paged_splitk as ps
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    cfg = model_config(sizes, 1)
    H, Hkv, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    W, bs, T = cfg.sliding_window, sizes.block_size, sizes.seq
    MB = sizes.max_context // bs
    key = jax.random.PRNGKey(seed)
    counter = iter(range(1 << 20))
    bf16, f32 = jnp.bfloat16, jnp.float32

    def rnd(*shape, dtype=bf16):
        return jax.random.normal(jax.random.fold_in(key, next(counter)),
                                 shape, f32).astype(dtype)

    failed: List[str] = []     # every kernel is tried; any failure fails
                               # the phase (and the run) at its end

    def run(name, kernel, reference, args, tol, expect_kernel=True):
        """Compile ``kernel`` as its own program, see the Mosaic call in it,
        run it, and compare every output with ``reference``'s."""
        compiled = jax.jit(kernel).lower(*args).compile()
        has_kernel = "tpu_custom_call" in compiled.as_text()
        got = jax.tree_util.tree_leaves(compiled(*args))
        ref = jax.tree_util.tree_leaves(jax.jit(reference)(*args))
        errs = [rel_err(g, r) for g, r in zip(got, ref)]
        finite = all(bool(jnp.isfinite(jnp.asarray(g, f32)).all())
                     for g in got)
        ok = (len(got) == len(ref) and finite and max(errs) <= tol
              and (has_kernel or not (on_chip and expect_kernel)))
        log(f"kernels/{name}: rel err {max(errs):.2e} (tol {tol:.0e})"
            + ("" if ok else f"  FAILED: errs {errs}, finite {finite}, "
                             f"Mosaic kernel in program {has_kernel}"))
        if not ok:
            failed.append(name)

    # -- flash attention, forward and backward, as the train step calls it:
    # [B, T, H, D] with the kv heads already repeated to H (models/llama.py).
    # The reference runs on the same values in f32 at the highest matmul
    # precision: on bf16 inputs it would round its scores to bf16 and be the
    # less accurate of the two.
    q, k, v = rnd(1, T, H, D), rnd(1, T, H, D), rnd(1, T, H, D)

    def sq(attn):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v, causal=True).astype(f32) ** 2)
        return loss

    def exact(fn):
        def ref(q, k, v):
            with jax.default_matmul_precision("highest"):
                return fn(q.astype(f32), k.astype(f32), v.astype(f32))
        return ref

    run("flash_fwd", lambda q, k, v: flash_attention(q, k, v, causal=True),
        exact(lambda q, k, v: reference_attention(q, k, v, causal=True)),
        (q, k, v), TOL_KERNEL)
    run("flash_bwd", jax.grad(sq(flash_attention), argnums=(0, 1, 2)),
        exact(jax.grad(sq(reference_attention), argnums=(0, 1, 2))),
        (q, k, v), TOL_KERNEL)
    del q, k, v

    # -- a paged pool: 8 sequences on distinct pages, contexts from one
    # token to past the window
    S, NB = 8, 8 * MB + 1
    bt = jnp.asarray(1 + np.random.RandomState(seed).permutation(NB - 1)
                     .reshape(S, MB), jnp.int32)
    longest = sizes.max_context - bs // 2
    ctx = jnp.asarray([1, bs - 28, bs, bs + 1, W // 4, W, W + W // 10,
                       longest], jnp.int32)
    pool = rnd(NB, 2, Hkv, bs, D)
    pool_i8, scales = pa.kv_quantize_rows(pool)
    tiles = pa.kv_scales_to_tiles(scales)          # the layout at rest
    pool_deq = pa.kv_dequantize_rows(pool_i8, scales)       # f32
    q = rnd(S, H, D)
    k_new, v_new = rnd(S, Hkv, D), rnd(S, Hkv, D)
    side_k, side_v = rnd(S, 16, Hkv, D), rnd(S, 16, Hkv, D)
    j = jnp.int32(5)
    NC, Cs = 4, 2 * bs                             # chunk slots, as served
    cq = rnd(NC, Cs, H, D)
    c0 = jnp.asarray([0, Cs, W - bs, longest - Cs - 3], jnp.int32)
    cbt, cctx = bt[:NC], c0 + Cs

    def tiles_to_logical(t):
        return t.reshape(NB, -1)[:, :2 * Hkv * bs].reshape(NB, 2, Hkv, bs)

    for wname, w in (("", None), ("_window", W)):
        run(f"decode{wname}",
            lambda q, kv, bt, cl: pa.paged_decode_attention(
                q, kv, bt, cl, window=w),
            lambda q, kv, bt, cl: pa.paged_decode_attention_reference(
                q, kv, bt, cl, window=w),
            (q, pool, bt, ctx), TOL_KERNEL)
        run(f"step{wname}",
            lambda q, kn, vn, kv, bt, cl: pa.paged_decode_attention_step(
                q, kn, vn, kv, bt, cl, window=w),
            lambda q, kn, vn, kv, bt, cl:
                pa.paged_decode_attention_step_reference(
                    q, kn, vn, kv, bt, cl, window=w),
            (q, k_new, v_new, pool, bt, ctx), TOL_KERNEL)
        run(f"chunk{wname}",
            lambda q, kv, bt, q0, cl: pa.paged_chunk_attention_batched(
                q, kv, bt, q0, cl, window=w),
            lambda q, kv, bt, q0, cl:
                pa.paged_chunk_attention_batched_reference(
                    q, kv, bt, q0, cl, window=w),
            (cq, pool, cbt, c0, cctx), TOL_KERNEL)
        run(f"sidebuf{wname}",
            lambda q, kv, bt, pl, sk, sv, j: pa.paged_decode_attention_sidebuf(
                q, kv, bt, pl, sk, sv, j, window=w),
            lambda q, kv, bt, pl, sk, sv, j:
                pa.paged_decode_attention_sidebuf_reference(
                    q, kv, bt, pl, sk, sv, j, window=w),
            (q, pool, bt, ctx, side_k, side_v, j), TOL_KERNEL)
        for n in (2, 4):
            run(f"splitk{n}{wname}",
                lambda q, kv, bt, cl: ps.paged_decode_attention_splitk(
                    q, kv, bt, cl, window=w, n_splits=n),
                lambda q, kv, bt, cl: pa.paged_decode_attention_reference(
                    q, kv, bt, cl, window=w),
                (q, pool, bt, ctx), TOL_KERNEL)
        # with a window the side-buffer split takes the XLA scan (its window
        # start is traced per sequence): checked, but there is no kernel
        run(f"sidebuf_splitk2{wname}",
            lambda q, kv, bt, pl, sk, sv, j: ps.paged_sidebuf_attention_splitk(
                q, kv, bt, pl, sk, sv, j, window=w, n_splits=2),
            lambda q, kv, bt, pl, sk, sv, j:
                pa.paged_decode_attention_sidebuf_reference(
                    q, kv, bt, pl, sk, sv, j, window=w),
            (q, pool, bt, ctx, side_k, side_v, j), TOL_KERNEL,
            expect_kernel=w is None)

        # int8 pages: the references attend over the dequantized pages; the
        # fused paths attend new rows at the value the page will store
        # (``deq``, the dequantized pool, is the references' argument only)
        i8 = (pool_i8, tiles, pool_deq)
        run(f"decode_int8{wname}",
            lambda q, kv, sc, deq, bt, cl: pa.paged_decode_attention(
                q, kv, bt, cl, window=w, kv_scales=sc),
            lambda q, kv, sc, deq, bt, cl:
                pa.paged_decode_attention_reference(q, deq, bt, cl, window=w),
            (q, *i8, bt, ctx), TOL_KERNEL_INT8)

        def step_int8(q, kn, vn, kv, sc, deq, bt, cl):
            out, kv2, sc2 = pa.paged_decode_attention_step(
                q, pa.kv_write_dequant(kn), pa.kv_write_dequant(vn), kv,
                bt, cl, window=w, kv_scales=sc)
            return out, pa.kv_dequantize_rows(kv2, tiles_to_logical(sc2))

        run(f"step_int8{wname}", step_int8,
            lambda q, kn, vn, kv, sc, deq, bt, cl:
                pa.paged_decode_attention_step_reference(
                    q, pa.kv_write_dequant(kn), pa.kv_write_dequant(vn),
                    deq, bt, cl, window=w),
            (q, k_new, v_new, *i8, bt, ctx), TOL_KERNEL_INT8)
        run(f"chunk_int8{wname}",
            lambda q, kv, sc, deq, bt, q0, cl:
                pa.paged_chunk_attention_batched(
                    q, kv, bt, q0, cl, window=w, kv_scales=sc),
            lambda q, kv, sc, deq, bt, q0, cl:
                pa.paged_chunk_attention_batched_reference(
                    q, deq, bt, q0, cl, window=w),
            (cq, *i8, cbt, c0, cctx), TOL_KERNEL_INT8)
        run(f"sidebuf_int8{wname}",
            lambda q, kv, sc, deq, bt, pl, sk, sv, j:
                pa.paged_decode_attention_sidebuf(
                    q, kv, bt, pl, sk, sv, j, window=w, kv_scales=sc),
            lambda q, kv, sc, deq, bt, pl, sk, sv, j:
                pa.paged_decode_attention_sidebuf_reference(
                    q, deq, bt, pl, sk, sv, j, window=w),
            (q, *i8, bt, ctx, side_k, side_v, j), TOL_KERNEL_INT8)
        for n in (2, 4):
            run(f"splitk{n}_int8{wname}",
                lambda q, kv, sc, deq, bt, cl:
                    ps.paged_decode_attention_splitk(
                        q, kv, bt, cl, window=w, kv_scales=sc, n_splits=n),
                lambda q, kv, sc, deq, bt, cl:
                    pa.paged_decode_attention_reference(
                        q, deq, bt, cl, window=w),
                (q, *i8, bt, ctx), TOL_KERNEL_INT8)

    # -- head_dim % 128 != 0 takes another decode kernel, which the TPU
    # compiler used to abort on at these shapes (repaired in PR 22; no model
    # of the smoke runs it, so it is checked here)
    for h, hkv, d in ((32, 8, 64), (32, 32, 80)):
        qs, ps_ = rnd(S, h, d), rnd(NB, 2, hkv, bs, d)
        run(f"decode_smalld_h{h}_kv{hkv}_d{d}",
            pa.paged_decode_attention, pa.paged_decode_attention_reference,
            (qs, ps_, bt, ctx), TOL_KERNEL)
    run("step_smalld_h32_kv8_d64", pa.paged_decode_attention_step,
        pa.paged_decode_attention_step_reference,
        (rnd(S, 32, 64), rnd(S, 8, 64), rnd(S, 8, 64), rnd(NB, 2, 8, bs, 64),
         bt, ctx), TOL_KERNEL)
    check(not failed, f"kernels: {len(failed)} failed: {failed}")


# --------------------------------------------------------------------------- #
# phase: train
# --------------------------------------------------------------------------- #

def train_batch_data(sizes: Sizes, seed: int, global_batch: int):
    import numpy as np
    vocab = model_config(sizes, 1).vocab_size
    ids = np.random.RandomState(seed).randint(
        0, vocab, (global_batch, sizes.seq)).astype(np.int32)
    return {"input_ids": ids}


def collective_counts(text: str) -> Dict[str, int]:
    """Collective instructions (sync or async-start) in compiled HLO text."""
    return {op: text.count(f" {op}(") + text.count(f" {op}-start(")
            for op in ("all-gather", "reduce-scatter", "all-reduce")}


def compiled_step_text(engine, batch) -> str:
    """Text of the program ``train_batch`` runs (the same jitted step, lowered
    for the same arguments — a persistent-cache hit)."""
    return engine._fused_step.lower(
        engine.state, engine._shard_global_batch(batch)).compile().as_text()


def run_training(sizes: Sizes, seed: int, layers: int, config: dict,
                 label: str, mesh_topology=None):
    """``initialize`` + one warm-up + TRAIN_STEPS ``train_batch`` steps on a
    repeated seeded batch. Returns (engine, losses, step seconds)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import deepspeed_tpu
    from deepspeed_tpu.models.llama import LlamaForCausalLM

    model = LlamaForCausalLM(model_config(sizes, layers, dtype=jnp.bfloat16,
                                          remat=True))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=config, rngs=jax.random.PRNGKey(seed),
        mesh_topology=mesh_topology)
    batch = train_batch_data(sizes, seed, config["train_batch_size"])
    t0 = time.time()
    losses = [float(engine.train_batch(batch))]        # warm-up: compiles
    log(f"{label}: warm-up step {time.time() - t0:.1f} s "
        f"(compile included), loss {losses[0]:.4f}")
    times = []
    for _ in range(TRAIN_STEPS):
        t0 = time.time()
        losses.append(float(engine.train_batch(batch)))   # float() blocks
        times.append(time.time() - t0)
    n_params = sum(x.size for x in
                   jax.tree_util.tree_leaves(engine.state["master"]))
    tokens = config["train_batch_size"] * sizes.seq
    log(f"{label}: depth {layers}, {n_params / 1e6:.1f}M parameters, "
        f"{tokens} tokens/step, losses "
        f"{' '.join(f'{x:.4f}' for x in losses)}")
    log(f"{label}: median step {float(np.median(times)):.3f} s -> "
        f"{tokens / float(np.median(times)):.0f} tokens/s (smoke figure)")
    check(bool(np.isfinite(losses).all()), f"{label}: non-finite loss")
    check(losses[-1] < losses[0] and
          all(b <= a + 1e-2 for a, b in zip(losses, losses[1:])),
          f"{label}: loss not falling: {losses}")
    return engine, losses, times


def phase_train(sizes: Sizes, seed: int, on_chip: bool = True) -> None:
    engine, _, _ = run_training(sizes, seed, sizes.train_layers,
                                train_config(global_batch=1), "train")
    if on_chip:
        text = compiled_step_text(
            engine, train_batch_data(sizes, seed, 1))
        n = text.count("tpu_custom_call")
        log(f"train: {n} Mosaic kernel calls in the compiled step")
        check(n > 0, "train: the compiled step holds no Pallas kernel "
                     "(attention fell to the dense path)")
    engine.destroy()


# --------------------------------------------------------------------------- #
# phase: serve
# --------------------------------------------------------------------------- #

def dense_reference(sizes: Sizes, layers: int, rows):
    """The model's own dense forward, as the reference for the engine's
    logits: f32 activations, jnp attention, matmuls at the highest precision.
    Returns a jitted ``f(params, ids [1, T]) -> logits[rows] [len(rows), V]``."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    ref_model = LlamaForCausalLM(model_config(sizes, layers,
                                              dtype=jnp.float32))
    rows = jnp.asarray(rows, jnp.int32)

    @jax.jit
    def dense_rows(params, ids):
        with jax.default_matmul_precision("highest"):
            logits = ref_model.apply({"params": params}, ids,
                                     method="forward_logits")
        return logits[0, rows]

    return dense_rows


def phase_serve(sizes: Sizes, seed: int, num_blocks: Optional[int] = None
                ) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.inference.v2.ragged.kv_cache import KVCacheConfig
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    from deepspeed_tpu.ops import attention as attention_ops
    from deepspeed_tpu.utils.tree import tree_cast, tree_size_bytes

    L = sizes.serve_layers
    cfg = model_config(sizes, L, dtype=jnp.bfloat16)
    model = LlamaForCausalLM(cfg)
    dev = jax.devices()[0]

    # -- weights: random from the seed, bf16, made in one jitted program
    probe = jnp.zeros((1, 8), jnp.int32)
    params = jax.jit(lambda k: tree_cast(model.init(k, probe)["params"],
                                         jnp.bfloat16))(jax.random.PRNGKey(seed))
    weight_bytes = tree_size_bytes(params)
    log(f"serve: depth {L} of {PUBLISHED_LAYERS}, weights "
        f"{weight_bytes / 2**30:.2f} GiB bf16")

    # -- the reference rows FIRST: two copies of the weights do not fit, and
    # the engine restacks its own
    Tp, K = sizes.ref_prompt, FORCED_TOKENS
    check(Tp + K < attention_ops.FLASH_MIN_SEQ
          or not attention_ops._use_pallas(),
          f"serve: a {Tp + K}-token reference would take the flash kernel, "
          "not jnp attention")
    rng = np.random.RandomState(seed + 1)
    prompt = rng.randint(0, cfg.vocab_size, (Tp,)).astype(np.int32)
    half = (Tp // 2 // sizes.block_size) * sizes.block_size or Tp // 2
    dense_rows = dense_reference(
        sizes, L, [half - 1] + list(range(Tp - 1, Tp + K)))

    # the forced tokens are the reference's own greedy continuation; causal
    # attention makes the rows at or before a position blind to the padding
    ids = np.zeros((1, Tp + K), np.int32)
    ids[0, :Tp] = prompt
    for i in range(K):
        ids[0, Tp + i] = int(jnp.argmax(dense_rows(params, ids)[1 + i]))
    ref = np.asarray(dense_rows(params, ids))       # [1 + K + 1, vocab]
    forced = ids[0, Tp:]
    check(bool(np.isfinite(ref).all()), "serve: non-finite reference logits")

    # -- the weights move to the host; the engine stacks its copy from there
    host_params = jax.device_get(params)
    del params
    gc.collect()

    if num_blocks is None:
        limit = dev.memory_stats()["bytes_limit"]
        budget = int(limit * HBM_FILL) - weight_bytes - HBM_HEADROOM
        num_blocks = KVCacheConfig.from_memory_budget(
            L, cfg.num_key_value_heads, cfg.head_dim, budget,
            block_size=sizes.block_size).num_blocks
    t0 = time.time()
    engine = InferenceEngineV2(model=model, model_parameters=host_params,
                               config=serve_config(sizes, num_blocks))
    del host_params
    pool_bytes = engine.kv.config.bytes_per_block() * (num_blocks + 1)
    log(f"serve: engine up in {time.time() - t0:.1f} s (warm-up included), "
        f"{num_blocks} blocks of {sizes.block_size} tokens = "
        f"{pool_bytes / 2**30:.2f} GiB of pages, window {engine.spec.window}, "
        f"{engine.compiles} programs built")
    check(engine.spec.window == cfg.sliding_window,
          "serve: the engine dropped the sliding window")

    # -- eight greedy requests through the frontend
    reqs = [(rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32), m)
            for n, m in zip(sizes.prompts, sizes.new_tokens)]
    compiles_before = engine.compiles
    traffic = CompileClock()     # also sees what the engine's counter cannot
    t0 = time.time()
    with engine.serving_frontend() as fe:
        handles = [fe.submit(p, priority="smoke", max_new_tokens=m)
                   for p, m in reqs]
        check(fe.drain(timeout=900.0), "serve: requests still in flight "
                                       "after 900 s")
    wall = time.time() - t0
    unseen, unseen_s = traffic.hits + traffic.misses, traffic.seconds
    for h, (p, m) in zip(handles, reqs):
        check(h.status == "finished" and len(h.tokens) == m,
              f"serve: request of {len(p)} prompt tokens ended "
              f"{h.status} with {len(h.tokens)} of {m} tokens")
        check(all(0 <= t < cfg.vocab_size for t in h.tokens),
              "serve: token outside the vocabulary")
    longest = max(len(p) + m for p, m in reqs)
    check(longest > cfg.sliding_window,
          "serve: no stream ran past the window")
    generated = sum(m for _, m in reqs)
    log(f"serve: {len(reqs)} requests finished in {wall:.1f} s, "
        f"{sum(len(p) for p, _ in reqs)} prompt + {generated} generated "
        f"tokens, longest stream {longest} tokens (window "
        f"{cfg.sliding_window}); {generated / wall:.0f} generated tokens/s "
        "(smoke figure)")
    check(engine.compiles == compiles_before,
          f"serve: {engine.compiles - compiles_before} compiles after "
          "warm-up")
    log("serve: 0 compiles after warm-up by the engine's counter; "
        f"{unseen} programs outside it were compiled or loaded during the "
        f"traffic ({unseen_s:.1f} s)")

    # -- logits, not tokens, against the dense forward
    off: List[str] = []

    def compare(name, got, want):
        got = np.asarray(got, np.float32)
        err = rel_err(got, want)
        log(f"serve/logits {name}: rel err {err:.2e} (tol {TOL_LOGITS:.0e})")
        if not (bool(np.isfinite(got).all()) and err <= TOL_LOGITS):
            off.append(name)

    uid = 1
    # a prompt from position 0 takes the packed prefill pass; its
    # continuation reads pages through the chunk kernel
    compare("prefill (packed pass)",
            engine.put([uid], [prompt[:half]])[0], ref[0])
    compare("prefill (paged chunk pass)",
            engine.put([uid], [prompt[half:]])[0], ref[1])
    for i in range(K):                              # paged decode kernel
        compare(f"decode step {i + 1} (ragged pass)",
                engine.put([uid], [forced[i:i + 1]])[0], ref[2 + i])
    engine.flush([uid])
    check(not off, f"serve/logits over tolerance or non-finite: {off}")

    # the fused decode-step program samples on the device and returns
    # tokens: each must be the reference's greedy token, or — at the first
    # one that is not, after which the histories differ — within the logits
    # tolerance of the reference's best
    uid = 2
    engine.put([uid], [prompt])
    toks = engine.decode_pipeline([uid]).run(K)[0]
    engine.flush([uid])
    scale = float(np.max(np.abs(ref)))
    for i, (got, want) in enumerate(zip(toks, forced)):
        if got != want:
            gap = float(ref[1 + i].max() - ref[1 + i][got])
            log(f"serve/fused step {i + 1}: token {got} for {want}, "
                f"reference gap {gap:.3e}")
            check(gap <= 2 * TOL_LOGITS * scale,
                  f"serve/fused step {i + 1}: token {got} is {gap} below "
                  "the reference's best logit")
            break
    else:
        log(f"serve/fused steps: {K} greedy tokens equal the reference's")


# --------------------------------------------------------------------------- #
# --multichip: ZeRO-3 sharded training over four chips
# --------------------------------------------------------------------------- #

def state_bytes_by_device(engine) -> Dict[int, int]:
    import jax
    held: Dict[int, int] = {}
    for leaf in jax.tree_util.tree_leaves(
            {k: engine.state[k] for k in ("master", "opt", "params")}):
        for sh in leaf.addressable_shards:
            held[sh.device.id] = held.get(sh.device.id, 0) + sh.data.nbytes
    return held


def phase_multichip(sizes: Sizes, seed: int, on_chip: bool = True) -> None:
    import jax
    import numpy as np
    from deepspeed_tpu.comm.mesh import build_topology
    from deepspeed_tpu.config import MeshConfig

    devices = jax.devices()
    n = len(devices)

    def sharded(layers: int, prefetch_depth: Optional[int]) -> List[float]:
        """One run over all chips; returns its loss stream."""
        label = (f"{'implicit' if prefetch_depth is None else 'prefetch-1'} "
                 f"fsdp={n} depth {layers}")
        engine, losses, _ = run_training(
            sizes, seed, layers,
            train_config(global_batch=n, fsdp=n,
                         prefetch_depth=prefetch_depth), label)
        check((engine._zero3_plan is not None) == (prefetch_depth is not None),
              f"{label}: explicit ZeRO-3 schedule armed = "
              f"{engine._zero3_plan is not None}")
        held = state_bytes_by_device(engine)
        total = sum(held.values())
        log(f"{label}: state bytes by device "
            f"{ {d: f'{b / 2**30:.2f} GiB' for d, b in sorted(held.items())} }"
            f"; {memory_line(devices)}")
        check(len(held) == n and
              all(abs(b / total - 1 / n) < 0.02 for b in held.values()),
              f"{label}: parameter + optimizer bytes are not a 1/{n} share "
              f"on every chip: {held}")
        if on_chip:
            in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
            check(max(in_use) < 1.25 * min(in_use),
                  f"{label}: device memory in use is uneven: {in_use}")
        text = compiled_step_text(engine, train_batch_data(sizes, seed, n))
        found = collective_counts(text)
        log(f"{label}: collectives in the compiled step {found}")
        check(found["all-gather"] > 0 and
              found["reduce-scatter"] + found["all-reduce"] > 0,
              f"{label}: no parameter gather or gradient reduction in the "
              "compiled step")
        if on_chip:
            check("tpu_custom_call" in text, f"{label}: no Pallas kernel in "
                                             "the compiled step")
        engine.destroy()
        del engine
        gc.collect()
        return losses

    def agree(what: str, a: List[float], b: List[float]) -> None:
        gap = max(abs(x - y) for x, y in zip(a, b))
        log(f"{what}: max loss gap {gap:.2e} (tol {TOL_LOSS})")
        check(gap <= TOL_LOSS, f"{what}: loss streams {a} and {b} disagree")

    # (c) first: the one-device comparison, at a depth that fits one chip,
    # the same seed and the same global batch (accumulated over n steps)
    one = build_topology(MeshConfig(data=1, fsdp=1), devices=devices[:1])
    cfg_one = train_config(global_batch=n)
    cfg_one["mesh"] = {"data": 1, "fsdp": 1}
    engine, ref, _ = run_training(sizes, seed, sizes.multi_ref_layers, cfg_one,
                                  "one device", mesh_topology=one)
    engine.destroy()
    del engine
    gc.collect()

    shallow, deep = sizes.multi_ref_layers, sizes.multi_layers
    agree(f"depth {shallow}, implicit vs one device",
          sharded(shallow, None), ref)
    agree(f"depth {shallow}, prefetch-1 vs one device",
          sharded(shallow, 1), ref)
    # (a), (b): the depth that needs four chips (its state does not fit one)
    agree(f"depth {deep}, implicit vs prefetch-1",
          sharded(deep, None), sharded(deep, 1))


# --------------------------------------------------------------------------- #

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run ONLY the four-chip ZeRO-3 path and what it is "
                         "compared with (needs four chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import jaxlib

    # -- phase device: a chip, or nothing
    devices = jax.devices()
    d0 = devices[0]
    want = 4 if args.multichip else 1
    log(f"device: platform {d0.platform}, kind {d0.device_kind}, count "
        f"{len(devices)}; jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
        f"libtpu {_libtpu_version()}, python {sys.version.split()[0]}")
    if d0.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {d0.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) != want:
        print(f"chip_smoke: needs {want} chip(s), found {len(devices)}",
              file=sys.stderr)
        return 2
    if os.environ.get("DSTPU_DISABLE_PALLAS"):
        print("chip_smoke: DSTPU_DISABLE_PALLAS is set; the kernels are what "
              "this run is for", file=sys.stderr)
        return 2

    from deepspeed_tpu.utils.compile_cache import setup_compile_cache
    # every program is persisted, however fast it compiled: a second run
    # against the same directory must then write nothing new
    cache_dir = setup_compile_cache(min_compile_time_secs=0.0)
    entries_before = cache_entries(cache_dir)
    log(f"compile cache: {cache_dir} "
        f"({'JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'default'}), "
        f"{entries_before} entries before")
    clock = CompileClock()

    if args.multichip:
        log(f"depth: {REAL.multi_layers} of 32 layers over {want} chips, "
            f"{REAL.multi_ref_layers} where one chip is compared")
        run_phase("multichip", lambda: phase_multichip(REAL, args.seed),
                  clock, devices)
    else:
        log(f"depth: train {REAL.train_layers}, serve {REAL.serve_layers} of "
            "32 layers; widths as published")
        run_phase("kernels", lambda: phase_kernels(REAL, args.seed),
                  clock, devices)
        run_phase("train", lambda: phase_train(REAL, args.seed),
                  clock, devices)
        gc.collect()
        run_phase("serve", lambda: phase_serve(REAL, args.seed),
                  clock, devices)

    entries_after = cache_entries(cache_dir)
    log(f"compile cache: {entries_after} entries after "
        f"({entries_after - entries_before} written), {clock.seconds:.1f} s "
        f"in backend compiles, hits {clock.hits}, misses {clock.misses}")
    log(f"total wall {time.time() - _T0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


def _libtpu_version() -> str:
    from importlib.metadata import PackageNotFoundError, version
    try:
        return version("libtpu")
    except PackageNotFoundError:
        return "not installed"


if __name__ == "__main__":
    sys.exit(main())
