"""Record a small device trace on the chip and print how it is laid out.

    python chipbench/tools/record_tiny_trace.py OUT_DIR

The trace kept under ``tests/chipbench/data`` was made by this script (see
PERF.md section 3 for what it showed: which planes are devices, which lines
hold operations and modules, and how kernels are named). It runs a jitted
step of two matmuls around the flash-attention kernel three times, with a
host sleep between the second and the third so that the device has an idle
gap under a harness annotation.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def dump(path: str, per_line: int = 12) -> None:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            if not events:
                continue
            print(f"  line {line.name!r}: {len(events)} events")
            seen = {}
            for ev in events:
                seen.setdefault(ev.name, ev)
            for name, ev in list(seen.items())[:per_line]:
                stats = {k: v for k, v in ev.stats}
                keep = {k: (str(v)[:60]) for k, v in stats.items()}
                print(f"    {name[:90]!r} start {ev.start_ns:.0f} "
                      f"dur {ev.duration_ns:.0f} stats {keep}")


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform!r}", file=sys.stderr)
        return 2

    @jax.jit
    def tiny_step(x, w):
        h = x @ w                                           # [T, H*D]
        q = h.reshape(1, x.shape[0], 8, 128)
        o = flash_attention(q, q, q, causal=True)
        return o.reshape(x.shape[0], -1) @ w.T

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    w = jnp.ones((1024, 1024), jnp.bfloat16) * 0.01
    tiny_step(x, w).block_until_ready()
    trace_dir = os.path.join(out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    with jax.profiler.trace(trace_dir):
        for i in range(3):
            if i == 2:
                with jax.profiler.TraceAnnotation("harness sleep"):
                    time.sleep(0.02)
            with jax.profiler.TraceAnnotation("harness step"):
                tiny_step(x, w).block_until_ready()
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(found) == 1, found
    kept = os.path.join(out_dir, "tiny_trace.xplane.pb")
    shutil.copy(found[0], kept)
    shutil.rmtree(trace_dir)
    print(f"kept {kept}: {os.path.getsize(kept)} bytes")
    dump(kept)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
