"""The two gated delta-rule kernels' shares of their rooflines with what they
are made of, from a ``--trace 2`` capture of a cell that runs them (on the
chip).

    python chipbench/tools/gdn_roofline.py --workload <cell> --seed N \\
        [--seconds 45]

Runs the cell as ``chipbench/run.py --trace 2`` does and prints, after the
result line, the readings the cell's ``gdn_step_roofline_share.*`` and
``gdn_scan_roofline_share.*`` metrics are taken from
(``chipbench/readers/gdn.py``: calls, microseconds a call, live rows, bytes
and operations a call, which bound is the larger); then the scan by itself,
after the run and in the same process, over a pass's own shapes with every
row live (host clock around ``block_until_ready``, 20 calls): in traffic a
pass's slots are partly filled, by itself the kernel is seen whole. Before
them, each serving program's table: milliseconds an execution under each
scope of :data:`SCOPES` (PERF.md section 5's table of the step).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_T_PROCESS = time.time()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


#: the scopes a program's table is cut by, the first that matches an
#: operation's ``op_name`` taking it
SCOPES = ("gdn/step", "gdn/scan", "gdn/conv", "gdn/gate_norm", "gdn",
          "attn_full", "attn", "moe_ffn/experts", "moe_ffn/shared", "moe_ffn",
          "ffn")


def program_tables(view) -> dict:
    """``program -> {"runs", "ms", scope: ms}``: device milliseconds an
    execution of each program in the capture, and of them the self time of
    the operations under each scope of :data:`SCOPES` (``other``: under
    none)."""
    from chipbench.harness import Registry
    from chipbench.reduce import hlo_names, named
    patterns = [(s, hlo_names.scope_pattern(s)) for s in SCOPES]
    tables, runs = {}, {}
    for dev in view["trace"].devices.values():
        for m in dev.modules:
            prog = named._program(m.name)
            runs[prog] = runs.get(prog, 0) + 1
    ops = Registry().module("readers", "gdn").program_ops
    for prog, name, _, t in ops(view["trace"], view["op_names"]):
        scope = next((s for s, pat in patterns if name and pat.search(name)),
                     "other")
        table = tables.setdefault(prog, {})
        table[scope] = table.get(scope, 0.0) + t * 1e-6
    return {prog: {"runs": runs[prog],
                   "ms": round(sum(table.values()) / runs[prog], 4),
                   **{k: round(v / runs[prog], 4) for k, v in sorted(
                       table.items(), key=lambda kv: -kv[1])}}
            for prog, table in tables.items()}


def scan_alone(config, peaks, calls: int = 20) -> dict:
    """``gdn_chunk_scan`` by itself over a pass's shapes, every row live."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench.reduce import gdn_work, mla_work
    from deepspeed_tpu.ops.pallas.gdn import gdn_chunk_scan
    w = gdn_work.widths(config)
    sm = config["engine"]["state_manager"]
    slot = sm["prefill_chunk_size"]
    slots = (sm["max_ragged_batch_size"]
             - sm["max_ragged_sequence_count"]) // slot
    T, Hk, Hv, N, P = (slots * slot, w["key_heads"], w["value_heads"],
                       w["d_key"], w["d_value"])
    rng = np.random.default_rng(0)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    bf = lambda x: jnp.asarray(x.reshape(T, -1), jnp.bfloat16)
    args = (bf(unit(rng.standard_normal((T, Hk, N))) * N ** -0.5),
            bf(unit(rng.standard_normal((T, Hk, N)))),
            bf(rng.standard_normal((T, Hv * P))),
            jnp.asarray(np.log(rng.uniform(0.5, 1.0, (T, Hv))), jnp.float32),
            jnp.asarray(rng.uniform(0.0, 1.0, (T, Hv)), jnp.float32),
            jnp.zeros((slots, N, Hv * P), jnp.float32),
            jnp.zeros((slots,), jnp.int32))
    scan = jax.jit(lambda *a: gdn_chunk_scan(*a, chunk=w["chunk"]))
    jax.block_until_ready(scan(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = scan(*args)
    jax.block_until_ready(out)
    seconds = (time.perf_counter() - t0) / calls
    flops, bytes_ = gdn_work.scan_call(T, slots, Hk, Hv, N, P, w["chunk"])
    return dict(mla_work.roofline(flops, bytes_, seconds, peaks),
                calls=calls, us_a_call=seconds * 1e6, tokens=T,
                flops_a_call=flops, bytes_a_call=bytes_,
                note="the whole jitted call: the kernel and the sums and "
                     "transposes it is handed")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=45.0)
    args = ap.parse_args(argv)

    from chipbench import harness
    reg = harness.Registry()
    cell = reg.cell(args.workload)
    devices, peaks = harness.gate_devices(
        int(cell["chips"]), os.path.join(reg.dir, "peaks.json"))
    from deepspeed_tpu.utils.compile_cache import setup_compile_cache
    setup_compile_cache(min_compile_time_secs=0.0)
    ctx = harness.Context(
        registry=reg, cell=cell, config=reg.config(cell["config"]),
        traffic=reg.traffic(cell["traffic"]), seed=args.seed,
        seconds=args.seconds, devices=devices, peaks=peaks,
        compiles=harness.CompileCounter(), t_process=_T_PROCESS)
    ctx.capture = harness.CaptureWindow(
        os.path.join(reg.root, "chipbench_out", "trace", args.workload),
        float(cell.get("trace_seconds", 2.0)))
    readers = reg.module("readers", "gdn")
    try:
        out = reg.driver(cell["driver"])(ctx)
        print(json.dumps(harness.result_line(ctx, out, 2)), flush=True)
        _, view = harness.load_view(ctx, out, 2, dict(out.end_to_end))
        for prog, table in program_tables(view).items():
            print(f"program {prog}: {json.dumps(table)}", flush=True)
        got = {"gdn_decode_step": readers.step_reading(view),
               "gdn_chunk_scan (traffic's passes, live rows)":
                   readers.scan_reading(view)}
    finally:
        ctx.capture.discard()
    got["gdn_chunk_scan (by itself, every row live)"] = scan_alone(
        ctx.config, peaks)
    for kernel, reading in got.items():
        print(f"{kernel}: {json.dumps(reading)}", flush=True)
    return 0 if all(got.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
