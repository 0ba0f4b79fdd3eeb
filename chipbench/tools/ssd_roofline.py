"""The two Mamba-2 (SSD) state kernels' shares of their rooflines, from a
``--trace 2`` capture of a cell that runs them (on the chip).

    python chipbench/tools/ssd_roofline.py --workload <cell> --seed N \\
        [--seconds 45]

Runs the cell as ``chipbench/run.py --trace 2`` does and, before the capture
is thrown away, reads from it every call of ``ssd_decode_step`` under the
decode-step programs and of ``ssd_chunk_scan`` under the prefill programs,
with its device time. What a call had to do comes from
``chipbench/reduce/ssd_work.py``: a decode call from the rows the decode
steps ran in the mean (sampled from the engine while the capture runs, as
``tools/mla_roofline.py`` does); a scan call from its program's prompt rows,
ALL counted as live — traffic fills them partly, so that reading is an upper
bound and is printed as one. So the scan is also timed by itself, after the
run and in the same process, over the pass's own shapes with every row live
(host clock around ``block_until_ready``, 20 calls): that reading is the
kernel's. Decode is held to 819 GB/s; the scan to the bfloat16 peak AND its
byte floor, whichever is higher (its float32 products take six MXU passes:
``ssd_work``'s docstring).

Not a metric of the benchmark, for ``tools/mla_roofline.py``'s reason
(PERF.md section 7 asks the next ``benchmark`` PR for it).
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

#: kernel -> the programs whose calls of it count
KERNELS = {"ssd_decode_step": ("jit_serve_decode_step",),
           "ssd_chunk_scan": ("jit_serve_prefill_packed",
                              "jit_serve_paged_pass")}


def kernel_calls(trace, op_names, kernel: str):
    """Device nanoseconds of every Mosaic call under the scope ``kernel``
    inside an execution of one of its programs."""
    from chipbench.reduce import hlo_names, named, xplane
    pattern = hlo_names.scope_pattern(kernel)
    for dev in trace.devices.values():
        mods, k = dev.modules, 0
        for ev, t in dev.self_times():
            while k + 1 < len(mods) and mods[k + 1].start_ns <= ev.start_ns:
                k += 1
            if not (mods and mods[k].start_ns <= ev.start_ns
                    <= mods[k].end_ns and xplane.is_mosaic(ev.name)
                    and named._program(mods[k].name).startswith(
                        KERNELS[kernel])):
                continue
            name = op_names.get(mods[k].name, {}).get(
                xplane.instruction(ev.name).lstrip("%"), "")
            if pattern.search(name):
                yield t


def widths(config) -> dict:
    heads, head = config["mamba_n_heads"], config["mamba_d_head"]
    state, taps = config["mamba_d_state"], config["mamba_d_conv"]
    conv = heads * head + 2 * config["mamba_n_groups"] * state
    return {"heads": heads, "d_head": head, "d_inner": heads * head,
            "d_state": state, "d_conv": taps,
            "conv_width": -(-conv // 1024) * 1024,
            "chunk": config["mamba_chunk_size"]}


def pass_rows(config):
    """(chunk slots of a prefill pass, rows a slot) of the cell's engine."""
    sm = config["engine"]["state_manager"]
    slot = sm["prefill_chunk_size"]
    return (sm["max_ragged_batch_size"]
            - sm["max_ragged_sequence_count"]) // slot, slot


def shares_of(view, config, samples) -> dict:
    """Both kernels' readings from a ``--trace 2`` view and the decode rows
    sampled while it was taken."""
    from chipbench.reduce import mla_work, ssd_work
    w, out = widths(config), {}
    calls = list(kernel_calls(view["trace"], view["op_names"],
                              "ssd_decode_step"))
    if calls and samples:
        rows = sum(r for r, _ in samples) / len(samples)
        ns = sum(calls) / len(calls)
        flops, bytes_ = ssd_work.decode_call(
            rows, w["d_inner"], w["d_state"], w["conv_width"], w["d_conv"])
        out["ssd_decode_step"] = dict(
            mla_work.roofline(flops, bytes_, ns * 1e-9, view["peaks"]),
            calls=len(calls), us_a_call=ns * 1e-3, rows=rows,
            us_a_row=ns * 1e-3 / rows, bytes_a_call=bytes_)
    calls = list(kernel_calls(view["trace"], view["op_names"],
                              "ssd_chunk_scan"))
    if calls:
        slots, slot = pass_rows(config)
        ns = sum(calls) / len(calls)
        flops, bytes_ = ssd_work.scan_call(
            slots * slot, slots, w["heads"], w["d_head"], w["d_state"],
            min(w["chunk"], slot))
        out["ssd_chunk_scan (traffic's passes; every prompt row counted "
            "live: an upper bound)"] = dict(
            mla_work.roofline(flops, bytes_, ns * 1e-9, view["peaks"]),
            calls=len(calls), us_a_call=ns * 1e-3, rows=slots * slot,
            flops_a_call=flops, bytes_a_call=bytes_)
    return out


def scan_alone(config, peaks, calls: int = 20) -> dict:
    """``ssd_chunk_scan`` by itself over a pass's shapes, every row live."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench.reduce import mla_work, ssd_work
    from deepspeed_tpu.ops.pallas.ssm import ssd_chunk_scan
    w = widths(config)
    slots, slot = pass_rows(config)
    T, H, E, N = slots * slot, w["heads"], w["d_inner"], w["d_state"]
    rng = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (T, H))),
                     jnp.float32)
    args = (dt, f(T, E), f(T, N), f(T, N),
            -jnp.asarray(rng.uniform(1, 16, (H,)), jnp.float32),
            f(slots, N, E), jnp.zeros((slots,), jnp.int32))
    scan = jax.jit(lambda *a: ssd_chunk_scan(*a, chunk=w["chunk"]))
    jax.block_until_ready(scan(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = scan(*args)
    jax.block_until_ready(out)
    seconds = (time.perf_counter() - t0) / calls
    flops, bytes_ = ssd_work.scan_call(T, slots, H, w["d_head"], N,
                                       min(w["chunk"], slot))
    return dict(mla_work.roofline(flops, bytes_, seconds, peaks),
                calls=calls, us_a_call=seconds * 1e6, rows=T,
                flops_a_call=flops, bytes_a_call=bytes_,
                note="the whole jitted call: the kernel and the sums, "
                     "transposes and dt x it is handed")


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
    sampled = reg.module("tools", "mla_roofline").sampled
    ctx.capture = sampled(harness.CaptureWindow)(
        os.path.join(reg.root, "chipbench_out", "trace", args.workload),
        float(cell.get("trace_seconds", 2.0)))
    driver = reg.module("drivers", cell["driver"])
    try:
        served = driver.bring_up(ctx)
        ctx.capture.engine = served.engine
        out = driver.serve(ctx, served)
        print(json.dumps(harness.result_line(ctx, out, 2)), flush=True)
        _, view = harness.load_view(ctx, out, 2, dict(out.end_to_end))
        got = shares_of(view, ctx.config, ctx.capture.samples)
    finally:
        ctx.capture.discard()
    got["ssd_chunk_scan (by itself, every row live)"] = scan_alone(
        ctx.config, peaks)
    for kernel, reading in got.items():
        print(f"{kernel}: {json.dumps(reading)}", flush=True)
    return 0 if all(r.get("share") for r in got.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
