"""Record a small device trace on the chip through the program's capture
control, and print where its names land.

    python chipbench/tools/record_scoped_trace.py OUT_DIR

Kept under ``tests/chipbench/data`` as ``tiny_trace_scoped.xplane.pb`` with
``tiny_trace_scoped.capture.json`` (what ``tracer.capture_stop`` returned:
the program's records on the trace's clock, the anchors' skew). PERF.md
section 3 has what it showed: a ``jax.named_scope`` is in neither an
operation's event name nor its statistics but in its ``op_name`` in the
program's HLO, which the trace carries on the plane ``/host:metadata``
(``chipbench/reduce/hlo_names.py`` reads it).

The step is the gradient of two matmuls around the flash-attention kernel, so
the forward and both backward kernels run under their scopes, jitted under
the name ``tiny_scoped_step``; it runs three times under ``tracer.span``, with
a host sleep (under a span of its own) before the third so that the device
has an idle gap a program span covers.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def dump(path: str, capture) -> None:
    from chipbench.reduce import hlo_names, xplane
    names = hlo_names.load(path)
    for program, instrs in names.items():
        print(f"program {program!r}: {len(instrs)} instructions with an "
              f"op_name")
        for instr, op_name in instrs.items():
            if "flash" in op_name or "pallas" in op_name:
                print(f"    {instr}: {op_name}")
    tr = xplane.load(path, ("host/step", "host/sleep"))
    for i, dev in tr.devices.items():
        print(f"device {i}: {len(dev.modules)} program runs "
              f"{sorted({m.name for m in dev.modules})}, {len(dev.ops)} "
              f"operations")
        for ev in dev.ops:
            if xplane.is_mosaic(ev.name):
                instr = xplane.instruction(ev.name).lstrip("%")
                op = next((n[instr] for n in names.values() if instr in n), "?")
                print(f"    mosaic {instr}: {ev.dur_ns * 1e-3:.1f} us, "
                      f"op_name {op}")
    # the same span twice: as the TraceAnnotation the profiler recorded and
    # as the ring record capture_stop mapped by the anchors
    ring = {r[2]: r for r in capture.records if r[1] == "host/step"}
    for ev, mapped in zip([e for e in tr.host if e.name == "host/step"],
                          sorted(ring)):
        print(f"host/step: annotation starts {ev.start_ns:.0f} ns, the ring "
              f"record maps to {mapped:.0f} ns ({mapped - ev.start_ns:+.0f})")
    dev0 = tr.devices[min(tr.devices)]
    steps = [e for e in tr.host if e.name == "host/step"]
    if steps and dev0.modules:
        print(f"first program run starts {dev0.modules[0].start_ns - steps[0].start_ns:+.0f}"
              f" ns from the start of the host span that dispatched it")


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.monitor.trace import tracer
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform!r}", file=sys.stderr)
        return 2

    def loss(x, w):
        h = x @ w                                           # [T, H*D]
        q = h.reshape(1, x.shape[0], 8, 128)
        o = flash_attention(q, q, q, causal=True)
        return jnp.sum((o.reshape(x.shape[0], -1) @ w.T).astype(jnp.float32))

    def tiny_scoped_step(x, w):
        return jax.grad(loss, argnums=1)(x, w)

    step = jax.jit(tiny_scoped_step)
    x = jnp.ones((2048, 1024), jnp.bfloat16)
    w = jnp.ones((1024, 1024), jnp.bfloat16) * 0.01
    step(x, w).block_until_ready()
    trace_dir = os.path.join(out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    tracer.capture_start(trace_dir)
    for i in range(3):
        if i == 2:
            with tracer.span("host/sleep"):
                time.sleep(0.02)
        t0 = time.perf_counter()
        with tracer.span("host/step", step=i):
            step(x, w).block_until_ready()
        tracer.add("host/step_added", t0, time.perf_counter(), step=i)
    capture = tracer.capture_stop()
    kept = os.path.join(out_dir, "tiny_trace_scoped.xplane.pb")
    shutil.copy(capture.trace_path, kept)
    shutil.rmtree(trace_dir)
    doc = {k: getattr(capture, k) for k in (
        "start_ns", "stop_ns", "perf_start", "drift", "skew_ns",
        "anchor_uncertainty_ns", "counters")}
    doc["records"] = [list(r) for r in capture.records]
    with open(os.path.join(out_dir, "tiny_trace_scoped.capture.json"),
              "w") as f:
        json.dump(doc, f, indent=1)
    print(f"kept {kept}: {os.path.getsize(kept)} bytes; capture skew "
          f"{capture.skew_ns:.0f} ns over "
          f"{(capture.stop_ns - capture.start_ns) * 1e-6:.1f} ms, anchors "
          f"known to {capture.anchor_uncertainty_ns:.0f} ns")
    dump(kept, capture)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
