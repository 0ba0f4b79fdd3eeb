"""The flash kernel's backward, timed by itself on the chip: the one fused
call (dq, dk and dv from each tile's scores formed once) against the two
older calls (``flash_bwd_dq`` + ``flash_bwd_dkv``), beside the forward call
as the control, by sequence length.

    chiprun --timeout 1500 -- python3 scripts/flash_bwd_table.py [--shapes 1x32x4096x128,...]

It is the table in PERF.md (PR 43) that set
``ops/pallas/flash_attention.py::FUSED_BWD_VMEM_BYTES``; run it again when the
kernel, the compiler or the chip changes. Each program is ONE call of
``_fwd`` or ``_bwd`` on ``[B, H, T, D]`` causal self-attention, run ``--calls``
times under a profiler capture; a line gives the microseconds its Mosaic
calls took a run (device time from the trace, the ``delta`` row sums beside
them not counted), the TFLOP/s over the products the mathematics needs of the
tiles that run (forward 2 a tile, backward 5), what
``_fused_bwd_vmem_bytes`` counts for the shape, and how far the fused call's
gradients are from the two calls'. A shape over the budget is timed fused all
the same (the budget lifted for that program) where the compiler takes it.
Lines also go to ``chiprun_out/flash_bwd_table.jsonl``. No chip, no number:
it exits 2.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench.reduce import flash_flops, xplane  # noqa: E402
from deepspeed_tpu.ops.pallas import flash_attention as fa  # noqa: E402

#: PERF.md's rows (PR 43): the training cells' shape first, then by length up
#: to the two lengths over the budget, then head size 64
SHAPES = ("1x32x4096x128,1x32x1024x128,1x32x2048x128,1x16x8192x128,"
          "1x8x16384x128,1x4x32768x128,1x2x65536x128,1x1x131072x128,"
          "1x1x262144x128,1x32x4096x64")


def mosaic_us(programs, calls: int):
    """name -> microseconds of Mosaic kernels a run, for ``programs``: name ->
    (jitted function, arguments). Every program is compiled and run once
    first; the capture holds ``calls`` runs of each."""
    for fn, args in programs.values():
        jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            for fn, args in programs.values():
                for _ in range(calls):
                    out = fn(*args)
                jax.block_until_ready(out)
        path, = glob.glob(os.path.join(tmp, "plugins/profile/*/*.xplane.pb"))
        trace = xplane.load(path)
    dev = next(iter(trace.devices.values()))
    total = dict.fromkeys(programs, 0.0)
    k = 0
    for ev, t in dev.self_times():
        while k + 1 < len(dev.modules) and \
                dev.modules[k + 1].start_ns <= ev.start_ns:
            k += 1
        name = xplane.module_name(dev.modules[k].name)[0]
        name = name[len("jit_"):] if name.startswith("jit_") else name
        if name in total and xplane.is_mosaic(ev.name):
            total[name] += t
    return {name: t / calls / 1e3 for name, t in total.items()}


def shape_programs(B, H, T, D, dtype):
    """The three programs of one shape, what ``_fused_bwd_vmem_bytes`` counts
    for it and whether that is inside the budget. ``bwd_fused`` is traced with
    the budget lifted to the count, ``bwd_split`` with a budget of 0."""
    ks = jax.random.split(jax.random.PRNGKey(T), 4)
    q, k, v, do = (jax.random.normal(key, (B, H, T, D), dtype) for key in ks)
    scale = D ** -0.5
    blocks = (fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K)

    def fwd(q, k, v):
        return fa._fwd(q, k, v, scale, True, *blocks)

    o, lse = jax.jit(fwd)(q, k, v)

    def bwd_at(name, vmem_bytes):
        def bwd(q, k, v, o, lse, do):
            was, fa.FUSED_BWD_VMEM_BYTES = fa.FUSED_BWD_VMEM_BYTES, vmem_bytes
            try:
                return fa._bwd(scale, True, *blocks, (q, k, v, o, lse), do)
            finally:
                fa.FUSED_BWD_VMEM_BYTES = was
        bwd.__name__ = name         # the program's name in the trace
        return jax.jit(bwd), (q, k, v, o, lse, do)

    need = fa._fused_bwd_vmem_bytes(T, D, fa._pick_block(T, blocks[0]),
                                    fa._pick_block(T, blocks[1]),
                                    jnp.dtype(dtype).itemsize)
    return {"fwd": (jax.jit(fwd), (q, k, v)),
            "bwd_fused": bwd_at("bwd_fused",
                                max(need, fa.FUSED_BWD_VMEM_BYTES)),
            "bwd_split": bwd_at("bwd_split", 0)}, \
        need, need <= fa.FUSED_BWD_VMEM_BYTES


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=SHAPES,
                    help="comma-separated BxHxTxD (causal self-attention)")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--calls", type=int, default=10)
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("flash_bwd_table: no TPU here, and a time comes only from one",
              file=sys.stderr)
        return 2
    dtype = jnp.dtype(args.dtype)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/flash_bwd_table.jsonl", "a") as out:
        for shape in args.shapes.split(","):
            B, H, T, D = (int(n) for n in shape.split("x"))
            programs, need, fits = shape_programs(B, H, T, D, dtype)
            line = {"shape": [B, H, T, D], "dtype": dtype.name,
                    "device": jax.devices()[0].device_kind,
                    "fused_vmem_bytes": need, "fits_the_budget": fits}
            try:
                us = mosaic_us(programs, args.calls)
                fused = programs["bwd_fused"][0](*programs["bwd_fused"][1])
                split = programs["bwd_split"][0](*programs["bwd_split"][1])
                line["fused_minus_split_max"] = [
                    float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                          - b.astype(jnp.float32))))
                    for a, b in zip(fused, split)]
            except Exception as e:  # the compiler's refusal of a shape is a row
                del programs["bwd_fused"]
                us = mosaic_us(programs, args.calls)
                line["fused_refused"] = f"{type(e).__name__}: {str(e)[:300]}"
            tile = flash_flops.call_flops("flash_fwd", B, H, T, D) / 2
            for name, products in (("fwd", 2), ("bwd_fused", 5),
                                   ("bwd_split", 5)):
                if name in us:
                    line[f"{name}_us"] = round(us[name], 1)
                    line[f"{name}_tflops"] = round(
                        products * tile / us[name] / 1e6, 1)
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
