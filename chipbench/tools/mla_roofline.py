"""The latent-attention decode kernel's share of its roofline, from a
``--trace 2`` capture of a cell that runs it (on the chip).

    python chipbench/tools/mla_roofline.py --workload <cell> --seed N \\
        [--seconds 45]

Runs the cell as ``chipbench/run.py --trace 2`` does and, before the capture
is thrown away, reads from it every call of the kernel ``mla_decode`` under
the decode-step programs, with its device time. What a call had to do comes
from the live contexts this tool samples while the capture runs (the
engine's sequences past their prompt and the tokens they hold, in the mean)
through ``chipbench/reduce/mla_work.py``. Prints the
cell's own result line, then one line for people: calls, microseconds a
call, operations and bytes a call, the share and which bound is the larger.

Not a metric of the benchmark: ``tests/chipbench/test_registry.py`` admits an
absent value only from the readers that are there, and a reader of this
would find nothing in the tiny recorded trace (PERF.md section 7 asks the
next ``benchmark`` PR for it).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

_T_PROCESS = time.time()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

KERNEL = "mla_decode"
PROGRAM = "jit_serve_decode_step"


def kernel_calls(trace, op_names):
    """Device nanoseconds of every Mosaic call under the scope ``KERNEL``
    inside an execution of a program named ``PROGRAM*`` (the paged pass
    calls the kernel too, for its few decode rows: not those)."""
    from chipbench.reduce import hlo_names, named, xplane
    pattern = hlo_names.scope_pattern(KERNEL)
    for dev in trace.devices.values():
        mods, k = dev.modules, 0
        for ev, t in dev.self_times():
            while k + 1 < len(mods) and mods[k + 1].start_ns <= ev.start_ns:
                k += 1
            if not (mods and mods[k].start_ns <= ev.start_ns
                    <= mods[k].end_ns and xplane.is_mosaic(ev.name)
                    and named._program(mods[k].name).startswith(PROGRAM)):
                continue
            name = op_names.get(mods[k].name, {}).get(
                xplane.instruction(ev.name).lstrip("%"), "")
            if pattern.search(name):
                yield t


def sampled(capture_class):
    """``capture_class`` (``harness.CaptureWindow``) that, while its capture
    runs, samples what the decode steps read from its ``engine``: the
    sequences whose prompt is through and the tokens they hold, as
    ``samples`` of ``(rows, tokens)``."""
    class Sampled(capture_class):
        engine = None

        def start(self):
            self.samples, self._done = [], threading.Event()
            self._sampler = threading.Thread(target=self._sample, daemon=True)
            super().start()
            self._sampler.start()

        def _sample(self):
            while not self._done.wait(0.005):
                try:
                    live = [s.seen_tokens for s in
                            list(self.engine.scheduler.seqs.values())
                            if not len(s.pending)]
                except RuntimeError:    # the engine thread changed the table
                    continue
                self.samples.append((len(live), sum(live)))

        def stop(self):
            if self.running:
                self._done.set()
                self._sampler.join()
            super().stop()

    return Sampled


def share_of(view, config, samples) -> dict:
    """The numbers of the module's docstring from a ``--trace 2`` view and
    the ``(rows, tokens)`` sampled while it was taken."""
    from chipbench.reduce import mla_work
    calls = list(kernel_calls(view["trace"], view["op_names"]))
    if not calls:
        return {}
    if not samples:
        return {"calls": len(calls)}
    rows, tokens = (sum(x) / len(samples) for x in zip(*samples))
    ns = sum(calls) / len(calls)
    # every row reads its own context; the mean call is the mean row count
    # at the mean context each
    flops, bytes_ = mla_work.decode_call(
        [tokens / rows] * max(1, round(rows)),
        config["num_attention_heads"], config["kv_lora_rank"],
        config["qk_rope_head_dim"], side_rows=1)
    return dict(mla_work.roofline(flops, bytes_, ns * 1e-9, view["peaks"]),
                calls=len(calls), us_a_call=ns * 1e-3, rows=rows,
                resident_tokens=tokens, flops_a_call=flops,
                bytes_a_call=bytes_)


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
        got = share_of(view, ctx.config, ctx.capture.samples)
    finally:
        ctx.capture.discard()
    print(f"{KERNEL} under {PROGRAM}*: {json.dumps(got)}", flush=True)
    return 0 if got.get("share") else 1


if __name__ == "__main__":
    sys.exit(main())
