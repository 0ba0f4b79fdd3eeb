"""Rehearse a cell on the CPU, where there is no chip (costs no chip time).

    python -m chipbench.rehearse --workload <cell> [--seconds 3] [--trace 0|2]

Runs the cell's own driver, reference check and traffic generator at a tiny
size: the configuration's widths shrunk (head_dim stays 128 so the kernel
variants are the real ones, interpreted), lengths divided by 16, four
virtual CPU devices for a four-chip cell. It finds wrong arguments, shapes
and control flow. It prints counts only — requests, tokens, steps, whether
the check passed — and never a time, a rate or a device metric's name: a
CPU run says nothing about them.

``--trace 2`` rehearses the run that measures and then traces: the driver's
extra segment under the program's capture, the reduction and every reader of
the cell's per-layer metrics. Its last line has a result line's form with the
values withheld: each metric says only whether its reader found something to
read (a CPU trace has no device plane, so device readers do not).
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

_T_PROCESS = time.time()

TINY_MODEL = dict(vocab_size=1024, hidden_size=512, intermediate_size=1024,
                  num_attention_heads=4, num_key_value_heads=2,
                  num_hidden_layers=2, max_position_embeddings=2048)
LENGTH_SCALE = 16
#: stands for ``memory_stats()["bytes_limit"]``, which the CPU backend lacks;
#: sized so that the tiny pool holds the tiny traffic
TINY_HBM = 96 << 20
#: counters that are counts, not times or shares of a device
COUNTS = ("decode_steps", "backlog_start", "backlog_end", "max_inflight",
          "compiles_in_window")


def _scaled(dist: dict) -> dict:
    out = dict(dist)
    for k in ("median", "min", "max"):
        if k in out:
            out[k] = max(2, int(out[k]) // LENGTH_SCALE)
    return out


def tiny(cell: dict, config: dict, traffic: dict):
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    config.update(TINY_MODEL)
    if config.get("sliding_window"):
        config["sliding_window"] = 256
    if config.get("num_local_experts"):
        config["num_local_experts"] = 4
    config["rehearsal_hbm_bytes"] = TINY_HBM
    config["hbm_headroom_bytes"] = 0
    if "engine" in config:
        sm = config["engine"]["state_manager"]
        sm.update(max_tracked_sequences=16, max_ragged_sequence_count=8,
                  max_ragged_batch_size=8 + 2 * 64, max_context=512,
                  prefill_chunk_size=64)
        config["engine"]["kv_cache"]["block_size"] = 64
        config["check"].update(prompt_tokens=200, forced_tokens=4)
    for key in ("prompt_tokens", "output_tokens"):
        if key in traffic:
            traffic[key] = _scaled(traffic[key])
    if "warmup" in traffic:
        traffic["warmup"] = dict(
            traffic["warmup"], requests=6,
            prompt_tokens=_scaled(traffic["warmup"]["prompt_tokens"]),
            output_tokens=_scaled(traffic["warmup"]["output_tokens"]))
    if "arrivals" in traffic:
        traffic["arrivals"]["rate_per_s"] = 2.0
    if "clients" in traffic:
        traffic.update(clients=4, pool_requests=16)
    if "ramp_s" in traffic:
        traffic.update(ramp_s=1.0, drain_s=600.0)
    if "seq_len" in traffic:
        traffic.update(seq_len=256, distinct_batches=2, warmup_steps=1)
    return config, traffic


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace", type=int, choices=(0, 2), default=0)
    args = ap.parse_args(argv)

    from chipbench.harness import (CaptureWindow, CompileCounter, Context,
                                   Registry)
    reg = Registry()
    cell = reg.cell(args.workload)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={int(cell['chips'])} "
        + os.environ.get("XLA_FLAGS", ""))
    import jax
    from deepspeed_tpu.utils.compile_cache import setup_compile_cache
    setup_compile_cache(min_compile_time_secs=0.5)
    config, traffic = tiny(cell, reg.config(cell["config"]),
                           reg.traffic(cell["traffic"]))
    ctx = Context(registry=reg, cell=cell, config=config, traffic=traffic,
                  seed=args.seed, seconds=args.seconds,
                  devices=jax.devices(), peaks={}, compiles=CompileCounter(),
                  t_process=_T_PROCESS, on_chip=False)
    if args.trace == 2:
        ctx.capture = CaptureWindow(
            os.path.join(reg.root, "chipbench_out", "rehearsal",
                         args.workload), min(1.0, args.seconds))
    out = reg.driver(cell["driver"])(ctx)
    print(f"rehearsal of {args.workload} on {len(jax.devices())} CPU "
          f"device(s), tiny widths: check and outputs correct "
          f"{out.correct}; attempted {out.attempted}, failed {out.failed}; "
          f"counts {({k: v for k, v in out.counters.items() if k in COUNTS})}")
    if args.trace == 2:
        try:
            print(json.dumps(rehearsal_line(ctx, out)), flush=True)
        finally:
            ctx.capture.discard()
    return 0 if out.correct else 1


def rehearsal_line(ctx, out) -> dict:
    """The form of a ``--trace 2`` result line, values withheld."""
    from chipbench.harness import BenchError, load_view
    reg, name = ctx.registry, ctx.cell["name"]
    values = dict(out.end_to_end, setup_s=0.0)
    _, view = load_view(ctx, out, 2, values)
    metrics = {}
    for m in reg.metrics_of(name, "end_to_end"):
        if m["name"] not in values:
            raise BenchError(f"the driver gave no {m['name']!r}")
        metrics[m["name"]] = {"unit": m["unit"], "read": True}
    for m in reg.metrics_of(name, "per_layer"):
        spec = reg.layer_metric(m["name"])
        try:
            value = reg.reader(spec["reader"])(view, **spec.get("args", {}))
        except (ValueError, KeyError):
            value = None        # no device plane, no peaks table: no chip
        metrics[m["name"]] = {"unit": m["unit"], "read": value is not None}
    d0 = ctx.devices[0]
    return {"rehearsal": True, "correct": bool(out.correct),
            "attempted": int(out.attempted), "failed": int(out.failed),
            "metrics": metrics,
            "device": {"platform": d0.platform, "kind": d0.device_kind,
                       "count": len(ctx.devices)}}


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.exit(main())
