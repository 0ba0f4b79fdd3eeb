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

Usage: ``python benchmarks/comm_bench.py [--sizes-mb 1,4,16,64] [--trials 20]``
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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes-mb", default="1,4,16,64")
    ap.add_argument("--trials", type=int, default=20)
    ap.add_argument("--dtype", default="bf16", choices=["bf16", "f32"])
    args = ap.parse_args()

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
