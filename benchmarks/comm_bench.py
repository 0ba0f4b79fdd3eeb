"""Collectives micro-benchmark over the device mesh.

Parity role: the reference's communication benchmark suite
(``benchmarks/README.md`` -> DeepSpeedExamples ``benchmarks/communication``:
all_reduce/all_gather/all_to_all/pt2pt sweeps printing algbw/busbw).  Here the
same sweep drives this framework's collectives API (``deepspeed_tpu.comm``)
over whatever mesh is available — N virtual CPU devices
(``--xla_force_host_platform_device_count``), one real chip (degenerate), or a
real slice — and prints one JSON line per (op, size).

Bus bandwidth uses the standard ring-algorithm correction factors the
reference's ``utils.calc_bw`` applies: allreduce 2(n-1)/n, allgather /
reducescatter (n-1)/n, alltoall (n-1)/n.

``--overlap`` runs the collective-overlap leg instead of the sweep: the same
bucketed all-gather issued (a) serially — each gather tied behind the previous
round's compute — and (b) pipelined one round ahead, the two-sided
tie-barrier/pin structure of the ZeRO-3 collective schedule
(``runtime/zero/prefetch.py``). Both programs carry in-jit
``jax.debug.callback`` stamps; the overlap fraction is measured from the
resulting gather/compute trace spans, not inferred from wall-clock deltas.
On a serial executor (1-core forced-host CPU) "overlap" is time-sliced window
interleaving — the schedule is still visible in the spans; wall-clock gains
need hardware that runs collectives async.

Usage: ``python benchmarks/comm_bench.py [--sizes-mb 1,4,16,64] [--trials 20]``
       ``python benchmarks/comm_bench.py --overlap [--sizes-mb 4] [--rounds 8]``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def run_overlap(args):
    """All-gather-under-compute vs serial gather-then-compute (A/B).

    Builds the same R-round program twice: each round all-gathers a sharded
    buffer and runs a matmul chain consuming it.  ``serial`` ties every
    gather behind the previous round's compute output (depth-0 schedule);
    ``pipelined`` issues gathers ``--depth`` rounds ahead and pins each
    round's compute input on a probe of the *next* round's gather (gather
    r+1 completes before compute r; deeper prefetches stay unpinned until
    their own consumer-minus-one round) — exactly the two-sided issue
    window ``scheduled_layer_walk`` compiles for ZeRO-3.
    Overlap fraction comes from in-jit stamp spans: gather windows
    intersected with OTHER rounds' residency windows (gather_end ->
    compute_start), the span-derived overlap discipline ``Zero3CommStats``
    uses for the training schedule.
    """
    import functools

    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.config import MeshConfig
    from deepspeed_tpu.utils.jax_compat import shard_map

    n = len(jax.devices())
    topo = dist.set_topology(dist.build_topology(MeshConfig(data=n)))
    mesh = topo.mesh
    dtype = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32
    itemsize = jnp.dtype(dtype).itemsize

    size_mb = float(args.sizes_mb.split(",")[0])
    numel = max(int(size_mb * 1e6 / itemsize) // n * n, n)
    R, iters = args.rounds, args.compute_iters
    m = 256
    while m * m > numel:
        m //= 2

    log = []

    def _rec(tag, _probe):
        log.append((tag, time.perf_counter()))

    def tap(x, tag):
        jax.debug.callback(functools.partial(_rec, tag), jnp.ravel(x)[:1])
        return x

    gather_sm = shard_map(
        lambda s: jax.lax.all_gather(s, "data", tiled=True),
        mesh=mesh, in_specs=(P("data"),), out_specs=P(None), check_vma=False)

    def tied(xs, t):
        # one barrier op over xs + a 1-elem probe of t: xs cannot become
        # available before t is — the issue-order tie (forward-only twin of
        # prefetch._tie_barrier; no AD needed here)
        out = jax.lax.optimization_barrier(tuple(xs) + (jnp.ravel(t)[:1],))
        return out[:-1]

    def build(depth):
        def prog(bufs, y0):
            y = y0
            pending = {}
            for r in range(R):
                for v in range(r, min(r + depth, R - 1) + 1):
                    if v not in pending:
                        (src,) = tied([bufs[v]], y)
                        src = tap(src, ("gs", v))
                        pending[v] = tap(gather_sm(src), ("ge", v))
                g = pending.pop(r)
                # completion pin one round ahead of use (the walk's deferred
                # pin): round r+1's gather must finish before compute r, while
                # deeper prefetches stay unpinned until their own r-1 — free
                # to run under intervening computes where collectives are
                # async
                if r + 1 in pending:
                    (y,) = tied([y], pending[r + 1])
                w = g[: m * m].reshape(m, m).astype(jnp.float32)
                y = tap(y, ("cs", r))
                for _ in range(iters):
                    y = jnp.tanh(y @ w)
                y = tap(y, ("ce", r))
            return y.sum()
        return jax.jit(prog)

    sharding = jax.sharding.NamedSharding(mesh, P("data"))
    bufs = [jax.device_put(jnp.asarray(np.random.randn(numel), dtype), sharding)
            for _ in range(R)]
    y0 = jnp.eye(m, dtype=jnp.float32) * 0.1

    for depth in (0, args.depth):
        fn = build(depth)
        fn(bufs, y0).block_until_ready()          # compile
        jax.effects_barrier()
        walls, fracs, g_tot, c_tot = [], [], 0.0, 0.0
        for _ in range(args.trials):
            log.clear()
            t0 = time.perf_counter()
            fn(bufs, y0).block_until_ready()
            walls.append(time.perf_counter() - t0)
            jax.effects_barrier()
            t = dict(log)
            gathers = [(t[("gs", r)], t[("ge", r)]) for r in range(R)]
            # residency = gather complete, compute not yet started: the
            # window a prefetched buffer sits parked.  Ending it at
            # compute_start (not compute_end) keeps the serial baseline
            # race-free: the next gather and the round-end tap become
            # ready at the same instant, so windows touching compute_end
            # would count executor tie-breaks as overlap.
            resident = [(t[("ge", r)], t[("cs", r)]) for r in range(R)]
            g_tot += sum(b - a for a, b in gathers)
            c_tot += sum(t[("ce", r)] - t[("cs", r)] for r in range(R))
            ov = 0.0
            for r, (a, b) in enumerate(gathers):
                merged = []
                for ra, rb in sorted(x for o, x in enumerate(resident)
                                     if o != r):
                    if merged and ra <= merged[-1][1]:
                        merged[-1] = (merged[-1][0], max(merged[-1][1], rb))
                    else:
                        merged.append((ra, rb))
                ov += sum(max(0.0, min(b, rb) - max(a, ra))
                          for ra, rb in merged)
            tot = sum(b - a for a, b in gathers)
            fracs.append(ov / tot if tot > 0 else 0.0)
        k = args.trials
        print(json.dumps({
            "op": "allgather_overlap",
            "mode": "serial" if depth == 0 else "pipelined",
            "depth": depth, "rounds": R,
            "size_mb": round(numel * itemsize / 1e6, 2), "devices": n,
            "wall_ms": round(float(np.median(walls)) * 1e3, 3),
            "gather_ms": round(g_tot / k * 1e3, 3),
            "compute_ms": round(c_tot / k * 1e3, 3),
            "overlap_frac": round(float(np.mean(fracs)), 4)}), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes-mb", default="1,4,16,64")
    ap.add_argument("--trials", type=int, default=20)
    ap.add_argument("--dtype", default="bf16", choices=["bf16", "f32"])
    ap.add_argument("--overlap", action="store_true",
                    help="run the gather-under-compute A/B leg instead of "
                         "the size sweep")
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--depth", type=int, default=1,
                    help="prefetch depth for the pipelined overlap leg")
    ap.add_argument("--compute-iters", type=int, default=16)
    args = ap.parse_args()

    if args.overlap:
        run_overlap(args)
        return

    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.config import MeshConfig

    n = len(jax.devices())
    topo = dist.set_topology(dist.build_topology(MeshConfig(data=n)))
    mesh = topo.mesh
    dtype = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32
    itemsize = jnp.dtype(dtype).itemsize

    from deepspeed_tpu.utils.jax_compat import shard_map

    def make(op):
        if op == "all_reduce":
            f = lambda x: jax.lax.psum(x, "data")
            spec_in = spec_out = P(None)
            corr = 2 * (n - 1) / n
        elif op == "all_gather":
            f = lambda x: jax.lax.all_gather(x, "data", tiled=True)
            spec_in, spec_out = P("data"), P(None)
            corr = (n - 1) / n
        elif op == "reduce_scatter":
            f = lambda x: jax.lax.psum_scatter(x, "data", tiled=True)
            spec_in, spec_out = P(None), P("data")
            corr = (n - 1) / n
        else:  # all_to_all
            f = lambda x: jax.lax.all_to_all(x.reshape(n, -1), "data", 0, 0,
                                             tiled=False).reshape(-1)
            spec_in = spec_out = P("data")
            corr = (n - 1) / n
        fn = jax.jit(shard_map(f, mesh=mesh, in_specs=(spec_in,),
                               out_specs=spec_out, check_vma=False))
        return fn, corr

    for size_mb in [float(x) for x in args.sizes_mb.split(",")]:
        numel = int(size_mb * 1e6 / itemsize)
        numel -= numel % (n * n)          # all_to_all divisibility
        x = jnp.asarray(np.random.randn(numel), dtype)
        for op in ("all_reduce", "all_gather", "reduce_scatter", "all_to_all"):
            fn, corr = make(op)
            out = fn(x)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(args.trials):
                out = fn(x)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / args.trials
            nbytes = numel * itemsize
            algbw = nbytes / dt / 1e9
            print(json.dumps({
                "op": op, "size_mb": round(nbytes / 1e6, 2),
                "devices": n, "latency_ms": round(dt * 1e3, 3),
                "algbw_GBps": round(algbw, 2),
                "busbw_GBps": round(algbw * corr, 2)}), flush=True)


if __name__ == "__main__":
    main()
