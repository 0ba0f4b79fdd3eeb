#!/usr/bin/env bash
# Bench smoke: keeps the serving (serving_bench.py --steady-state) and
# training (train_bench.py) pipeline legs RUNNABLE on a CPU-only box (tiny
# models, tiny sizes, <60 s each warm) so neither can rot between hardware
# rounds.
#
# Exit status reflects the legs' own correctness gates (serving:
# byte-identical greedy streams + one-token-row per-step transfer; training:
# byte-identical loss streams + zero warm-loop compiles). Throughput numbers
# at these sizes are smoke, not signal — real numbers come from the full legs
# (docs/SERVING.md, docs/TRAINING.md). tier1.sh invokes this NON-FATALLY
# after pytest.
#
# Every leg runs with span tracing ON (DSTPU_TRACE -> docs/OBSERVABILITY.md),
# so the byte-equality / zero-recompile gates double as "tracing changes
# nothing" gates; trace_check.py then validates the emitted timelines —
# Chrome-trace schema, four subsystems on distinct tracks, and the --preempt
# kill's flight-recorder dump.
set -o pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

TRACE_DIR="$(mktemp -d "${TMPDIR:-/tmp}/dstpu_trace.XXXXXX")"
trap 'rm -rf "$TRACE_DIR"' EXIT
export DSTPU_TRACE="$TRACE_DIR"

timeout -k 10 300 python benchmarks/serving_bench.py --steady-state \
    --seqs 4 --prompt 16 --gen 24 || exit 1

# SLO-aware frontend leg (docs/SERVING.md "Frontend"): a few dozen Poisson
# arrivals against the persistent server, gating stream byte-equality vs
# direct pipeline runs, zero steady-state compiles, and one forced
# preempt-offload-restore cycle; emits serve/req per-request trace lanes
timeout -k 10 300 python benchmarks/serving_bench.py --frontend --smoke \
    || exit 1

# quantized-KV leg (docs/SERVING.md "Quantized KV"): the same seeded
# Poisson workload against an fp32 pool and an int8 pool sized from ONE
# byte budget, both with prefix cache AND spec decode enabled — gating
# byte-identical quantized streams across cache-hit / spec-on-off /
# preempt-offload-restore paths, zero timed compiles, and the bytes/token
# + pool-blocks capacity drop (goodput medians gate full-size, BENCH_r15)
timeout -k 10 600 python benchmarks/serving_bench.py --frontend --smoke \
    --kv-dtype int8 || exit 1

# speculative-decoding leg (docs/SERVING.md "Speculative decoding"):
# spec-off DecodePipeline vs draft-and-verify SpecDecodePipeline on one
# warmed engine, gating byte-identical greedy streams, zero compiles across
# the (bucket, k) verify grid, and allocator blocks back to baseline after
# reject-heavy runs; emits serve/spec trace lanes (smoke: correctness
# gates only — the >=1.5x repetitive-leg ratio runs full-size, BENCH_r12)
timeout -k 10 300 python benchmarks/serving_bench.py --spec --smoke \
    --spec-k 7 || exit 1

# flash-decoding long-context leg (docs/SERVING.md "Attention kernels"):
# few sequences x long ctx on ONE engine warmed across the pow2 split
# ladder — split=1 (chunk-serial) vs auto rung selection, gating identical
# token streams, zero timed compiles, allocator baseline and ladder
# engagement; emits the serve/attn rung-selection trace lane trace_check
# requires below (the >=1.3x op-level split-K bar runs full-size,
# BENCH_r17)
timeout -k 10 300 python benchmarks/serving_bench.py --long-context \
    --smoke || exit 1

# multi-replica router leg (docs/SERVING.md "Multi-replica &
# disaggregation"): 2 replicas behind a ServingRouter on a seeded
# shared-prefix Poisson stream, correctness gates only — every checked
# stream byte-identical to a direct single-frontend run, at least one
# forced prefill->decode KV handoff over the page fabric, zero
# steady-state compiles on every replica; emits serve/router trace lanes.
# DSTPU_LOCKSAN=1 arms the runtime lock-order sanitizer
# (docs/THREADLINT.md): the leg additionally gates zero observed
# acquisition cycles and static-graph coverage of every observed edge,
# with the byte-equality / zero-compile gates unchanged — the sanitized
# locks must not alter behavior
DSTPU_LOCKSAN=1 timeout -k 10 300 \
    python benchmarks/serving_bench.py --router --smoke || exit 1

# multi-tenant LoRA leg (docs/SERVING.md "Multi-tenant LoRA"): a seeded
# Poisson mix drawing tenants from more registered adapters than the
# adapter pool holds — correctness gates only (byte-identical mixed-batch
# streams vs direct per-adapter runs, zero compiles across adapter churn,
# allocator + adapter pool at baseline; the >=1.5x goodput-vs-naive gate
# runs full-size, BENCH_r18); the cold-adapter fault-ins emit the
# serve/lora trace lane trace_check requires below
timeout -k 10 300 python benchmarks/serving_bench.py --lora --smoke \
    || exit 1

# fault-tolerance leg (docs/SERVING.md "Failure semantics"): 2 replicas
# behind a health-monitored router replay a seeded Poisson stream while
# fault injection kills one serving loop and stalls the other — gating
# byte-identical non-shed streams vs uninterrupted references, detection of
# both failure modes, migration, self-healing rejoin with zero compiles,
# and allocator baseline on every replica; the injected raise also leaves
# the flight-recorder dump trace_check verifies below. Runs lock-order
# sanitized (DSTPU_LOCKSAN=1) — the failover/rejoin storm is the stack's
# richest locking workload, and the injected raise's crash dump carries
# the locksan report (docs/OBSERVABILITY.md)
DSTPU_LOCKSAN=1 timeout -k 10 300 \
    python benchmarks/serving_bench.py --chaos --smoke || exit 1

timeout -k 10 300 python benchmarks/train_bench.py --smoke || exit 1

# offloaded-optimizer pipeline leg: serial vs overlapped host step through
# the same engine, gating byte-identical loss streams + zero warm compiles
timeout -k 10 300 python benchmarks/train_bench.py --smoke --offload || exit 1

# preemption-tolerance leg (docs/ELASTICITY.md): kill a subprocess run at a
# non-checkpoint step AND mid-checkpoint-write, resume each onto a different
# simulated device count, gating byte-identical resumed loss streams + torn
# checkpoint fallback + zero post-resume-warmup compiles. The kills also
# exercise the tracer's flight recorder (trace_crash.json).
timeout -k 10 300 python benchmarks/train_bench.py --smoke --preempt || exit 1

# tracer-overhead leg: trace-off vs trace-on through the same pipelined
# loop; correctness gates here, the <=5% bar runs full-size (BENCH_r10)
timeout -k 10 300 python benchmarks/train_bench.py --smoke --trace-overhead \
    || exit 1

# ZeRO-3 collective-schedule leg (docs/TRAINING.md "ZeRO-3 collective
# schedule"): prefetch depth 0 vs 1/2 over an 8-way forced-host fsdp mesh —
# gating byte-identical loss streams across depths and zero timed compiles
# (the >=1.15x steps/sec bar applies on async-collective hardware, BENCH_r16;
# hidden collective time is read from a device trace, not here)
timeout -k 10 300 python benchmarks/train_bench.py --smoke --zero3-overlap \
    || exit 1

# colocated-rollout leg (docs/TRAINING.md "Colocated rollout"): one
# train+serve pair on the same devices — the WeightBridge's device-resident
# reshard vs the universal-checkpoint round-trip (byte-equal weights),
# >=3 in-place swaps into a warmed engine (zero new compiles, post-swap
# greedy streams byte-identical to a freshly built engine, KV allocator at
# baseline), and the full RolloutLoop vs rebuild-per-update (byte-identical
# rollouts); emits the train/rollout trace lanes trace_check requires below
# (the >=5x sync bar runs full-size, BENCH_r19)
timeout -k 10 300 python benchmarks/rollout_bench.py --smoke || exit 1

# serving-side tracer/attribution overhead leg (docs/OBSERVABILITY.md):
# the same router workload with flow tracing + phase attribution ON vs
# OFF; correctness gates here (byte-identical streams, zero compiles),
# the <=2% bar runs full-size (BENCH_r16)
timeout -k 10 300 python benchmarks/serving_bench.py --trace-overhead \
    --smoke || exit 1

# the timelines the legs above emitted: schema-valid, spans from the train
# pipeline, decode pipeline, serving-frontend request lanes, speculative
# decode, multi-replica router, checkpoint, and offload subsystems on
# distinct tracks, cross-lane request flow chains (--require-flows: the
# router/chaos legs bind each request's hops by trace_id), plus a
# parseable flight-recorder dump from the --preempt kills
timeout -k 10 120 python scripts/trace_check.py "$TRACE_DIR" \
    --require train serve serve/req serve/spec serve/router serve/health \
    serve/lora serve/attn ckpt train/offload train/rollout \
    --require-flows serve/req \
    --expect-crash || exit 1

# clock-align + merge the per-process trace files into one timeline; the
# merged file must pass the same flow-aware checks (stitched chains keep
# exactly one s/f per id)
timeout -k 10 120 python scripts/trace_merge.py "$TRACE_DIR" \
    -o "$TRACE_DIR/trace_merged.json" || exit 1
timeout -k 10 120 python scripts/trace_check.py \
    "$TRACE_DIR/trace_merged.json" --require-flows serve/req || exit 1

# per-request waterfall over the emitted traces: at least one multi-hop
# request chain must exist and render (the SLO-miss debugging workflow,
# docs/OBSERVABILITY.md "SLO-miss attribution")
timeout -k 10 120 python scripts/request_autopsy.py "$TRACE_DIR" --smoke \
    || exit 1
