"""The experts' grouped products' share of their roofline, and both Mamba-2
(SSD) state kernels' with their groups of B and C counted, from a ``--trace
2`` capture of a cell that runs them (on the chip).

    python chipbench/tools/moe_roofline.py --workload <cell> --seed N \\
        [--seconds 45]

Runs the cell as ``chipbench/run.py --trace 2`` does (``tools/
ssd_roofline.py``'s way, whose sampler and call reader this uses) and, before
the capture is thrown away, reads every call of the Pallas grouped matmul
(scope ``moe_grouped_matmul``) and of XLA's ``ragged_dot`` kernel
(``%ragged-dot*`` by instruction name) under the decode-step programs and
under the prefill programs, with its device time. What a call had to do comes
from ``chipbench/reduce/moe_work.py``, counted on the PUBLISHED shapes (the
configuration's ``hidden_size`` and ``moe_intermediate_size``, not a padded
stack's): in a decode step the rows the steps ran in the mean (sampled from
the engine while the capture runs) times ``top_k``, of which the held share
lands here, on the held experts the engine's routers reach
(``serve/moe/held_touched_share``, measured at bring-up on unit-normal
rows: the traffic's own rows, which share much of their direction, reach
fewer, so that reading too is an upper bound and may pass 100%); in a prefill
pass
every prompt row counted live — an upper bound, printed as one — on every
held expert. Decode is held to the HBM rate, prefill to the bfloat16 peak or
its bytes, whichever is more. The SSD kernels' readings are
``tools/ssd_roofline.py``'s with ``moe_work``'s group counts.

Not a metric of the benchmark, for ``tools/mla_roofline.py``'s reason
(PERF.md section 7).
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

DECODE = ("jit_serve_decode_step",)
PREFILL = ("jit_serve_prefill_packed", "jit_serve_paged_pass")
#: configuration keys of the families whose cells this reads, by what they
#: name: (Mamba heads, head size, state, taps, groups, chunk)
SSD_KEYS = (("mamba_num_heads", "mamba_head_dim", "ssm_state_size",
             "conv_kernel", "n_groups", "chunk_size"),
            ("mamba_n_heads", "mamba_d_head", "mamba_d_state",
             "mamba_d_conv", "mamba_n_groups", "mamba_chunk_size"))
MOE_KEYS = (("moe_intermediate_size", "n_routed_experts"),
            ("intermediate_size", "num_local_experts"))


def grouped_calls(trace, op_names, programs):
    """Device nanoseconds of every grouped product (the Pallas kernel's
    calls under the scope ``moe_grouped_matmul``, XLA's ``%ragged-dot*``
    fusions) inside an execution of one of ``programs``."""
    from chipbench.reduce import hlo_names, named, xplane
    pattern = hlo_names.scope_pattern("moe_grouped_matmul")
    for dev in trace.devices.values():
        mods, k = dev.modules, 0
        for ev, t in dev.self_times():
            while k + 1 < len(mods) and mods[k + 1].start_ns <= ev.start_ns:
                k += 1
            if not (mods and mods[k].start_ns <= ev.start_ns
                    <= mods[k].end_ns and named._program(
                        mods[k].name).startswith(programs)):
                continue
            instruction = xplane.instruction(ev.name)
            name = op_names.get(mods[k].name, {}).get(
                instruction.lstrip("%"), "")
            if instruction.startswith("%ragged-dot") or (
                    xplane.is_mosaic(ev.name) and pattern.search(name)):
                yield t


def _keys(config, keys):
    for names in keys:
        if all(k in config for k in names):
            return names
    raise KeyError(f"none of {keys} in the configuration")


def widths(config) -> dict:
    heads, head, state, taps, groups, chunk = (
        config[k] for k in _keys(config, SSD_KEYS))
    conv = heads * head + 2 * groups * state
    width_key, held_key = _keys(config, MOE_KEYS)
    held = config[held_key]
    return {"heads": heads, "d_head": head, "d_inner": heads * head,
            "d_state": state, "d_conv": taps, "groups": groups,
            "conv_width": -(-conv // 1024) * 1024, "chunk": chunk,
            "hidden": config["hidden_size"], "width": config[width_key],
            "held": held,
            "routed": config.get("published", {}).get(held_key, held),
            "top_k": config["num_experts_per_tok"]}


def shares_of(view, config, samples, touched_share: float) -> dict:
    """The grouped products' and both SSD kernels' readings from a ``--trace
    2`` view, the decode rows sampled while it was taken and the share of
    the held experts a decode step reaches."""
    from chipbench.harness import Registry
    from chipbench.reduce import mla_work, moe_work
    ssd = Registry().module("tools", "ssd_roofline")
    w, out = widths(config), {}
    rows = sum(r for r, _ in samples) / len(samples) if samples else 0.0
    on_held = w["top_k"] * w["held"] / w["routed"]      # assignments a row
    calls = list(grouped_calls(view["trace"], view["op_names"], DECODE))
    if calls and rows:
        ns = sum(calls) / len(calls)
        flops, bytes_ = moe_work.grouped_product(
            rows * on_held, touched_share * w["held"], w["hidden"],
            w["width"])
        out["grouped product (decode steps; the experts touched are the "
            "bring-up's, of unit-normal rows: traffic's own rows reach "
            "fewer, so an upper bound)"] = dict(
            mla_work.roofline(flops, bytes_, ns * 1e-9, view["peaks"]),
            calls=len(calls), us_a_call=ns * 1e-3, rows=rows * on_held,
            experts_touched=touched_share * w["held"], bytes_a_call=bytes_,
            gb_s=bytes_ / ns)
    slots, slot = ssd.pass_rows(config)
    calls = list(grouped_calls(view["trace"], view["op_names"], PREFILL))
    if calls:
        ns = sum(calls) / len(calls)
        flops, bytes_ = moe_work.grouped_product(
            slots * slot * on_held, w["held"], w["hidden"], w["width"])
        out["grouped product (traffic's prefill passes; every prompt row "
            "counted live: an upper bound)"] = dict(
            mla_work.roofline(flops, bytes_, ns * 1e-9, view["peaks"]),
            calls=len(calls), us_a_call=ns * 1e-3,
            rows=slots * slot * on_held, flops_a_call=flops,
            bytes_a_call=bytes_)
    calls = list(ssd.kernel_calls(view["trace"], view["op_names"],
                                  "ssd_decode_step"))
    if calls and rows:
        ns = sum(calls) / len(calls)
        flops, bytes_ = moe_work.ssd_decode_call(
            rows, w["d_inner"], w["d_state"], w["conv_width"], w["d_conv"],
            w["groups"])
        out["ssd_decode_step"] = dict(
            mla_work.roofline(flops, bytes_, ns * 1e-9, view["peaks"]),
            calls=len(calls), us_a_call=ns * 1e-3, rows=rows,
            us_a_row=ns * 1e-3 / rows, bytes_a_call=bytes_,
            groups=w["groups"])
    calls = list(ssd.kernel_calls(view["trace"], view["op_names"],
                                  "ssd_chunk_scan"))
    if calls:
        ns = sum(calls) / len(calls)
        flops, bytes_ = moe_work.ssd_scan_call(
            slots * slot, slots, w["heads"], w["d_head"], w["d_state"],
            min(w["chunk"], slot), w["groups"])
        out["ssd_chunk_scan (traffic's passes; every prompt row counted "
            "live: an upper bound)"] = dict(
            mla_work.roofline(flops, bytes_, ns * 1e-9, view["peaks"]),
            calls=len(calls), us_a_call=ns * 1e-3, rows=slots * slot,
            flops_a_call=flops, bytes_a_call=bytes_, groups=w["groups"])
    return out


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
    from deepspeed_tpu.monitor.trace import tracer
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
        got = shares_of(view, ctx.config, ctx.capture.samples,
                        tracer.totals.get("serve/moe/held_touched_share",
                                          1.0))
    finally:
        ctx.capture.discard()
    for kernel, reading in got.items():
        print(f"{kernel}: {json.dumps(reading)}", flush=True)
    return 0 if got and all(r.get("share") for r in got.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
