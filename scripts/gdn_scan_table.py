"""``gdn_chunk_scan`` timed by itself on the chip at Qwen3-Next's widths (16
key heads of 128 serving 32 value heads of 128, a pass of 8 chunk slots of 256
rows, bfloat16 activations), by how full the pass is.

    chiprun --timeout 1500 -- python3 scripts/gdn_scan_table.py [--other path/to/gdn.py ...]

It is the table in PERF.md (PR 48); run it again when the kernel, the
compiler or the chip changes. A line gives, for one version of the module and
one filling of the slots, the microseconds of the Mosaic call a run (device
time from a profiler capture), of the whole jitted call (host clock around
``block_until_ready``: the kernel and the sums and transposes it is handed)
and how far its output and last state are from the recurrent twin's and from
this tree's kernel's. Fillings: ``live`` every row a token; ``empty`` the last
slot all padding (``g = 0``, ``beta = 0``: the chunks that take the short
way); ``unflagged`` the same slot with ``g = -1e-30`` (the same arithmetic
down the long way: what a chunk of zeros cost before it was flagged);
``ragged`` three slots part full (1,710 of 2,048 rows live, three of the 32
chunks all padding, as cell 11's passes have in the mean). ``--other`` names
further copies of ``ops/pallas/gdn.py`` to time beside this tree's (a
parent's, unpacked from ``git archive``); ``--unpacked`` also times this
tree's with one value head an inverse chain. Lines also go to
``chiprun_out/gdn_scan_table.jsonl``. No chip, no number: it exits 2.
"""

from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench.reduce import xplane  # noqa: E402
from deepspeed_tpu.ops.pallas import gdn  # noqa: E402

T, HK, HV, N, P, SLOTS = 2048, 16, 32, 128, 128, 8
FILLINGS = ("live", "empty", "unflagged", "ragged")


def arguments(filling: str, seed: int = 48):
    """One pass's arguments: unit keys, scaled unit queries, a slow head in
    four (``exp(g)`` 0.98-1, as the configuration draws them)."""
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    bf = lambda x: jnp.asarray(x.reshape(T, -1), jnp.bfloat16)
    g = np.log(rng.uniform(0.5, 1.0, (T, HV)))
    g[:, ::4] = np.log(rng.uniform(0.98, 1.0, g[:, ::4].shape))
    beta = rng.uniform(0.0, 1.0, (T, HV))
    rows = T // SLOTS
    dead = {"live": (), "empty": ((SLOTS - 1, 0),),
            "unflagged": ((SLOTS - 1, 0),),
            "ragged": ((3, 200), (6, 130), (7, 100))}[filling]
    for slot, held in dead:
        pad = slice(slot * rows + held, (slot + 1) * rows)
        g[pad], beta[pad] = (-1e-30 if filling == "unflagged" else 0.0), 0.0
    return (bf(unit(rng.standard_normal((T, HK, N))) * N ** -0.5),
            bf(unit(rng.standard_normal((T, HK, N)))),
            bf(rng.standard_normal((T, HV * P))),
            jnp.asarray(g, jnp.float32), jnp.asarray(beta, jnp.float32),
            jnp.asarray(rng.standard_normal((SLOTS, N, HV * P)), jnp.float32),
            jnp.asarray([0, 1, 0, 0, 1, 1, 0, 0], jnp.int32))


def mosaic_us(fn, args, calls: int) -> float:
    """Microseconds of Mosaic kernels a run of ``fn``, from a capture."""
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            for _ in range(calls):
                out = fn(*args)
            jax.block_until_ready(out)
        path, = glob.glob(os.path.join(tmp, "plugins/profile/*/*.xplane.pb"))
        dev = next(iter(xplane.load(path).devices.values()))
    return sum(t for ev, t in dev.self_times()
               if xplane.is_mosaic(ev.name)) / calls / 1e3


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / (np.mean(b ** 2) + 1e-30)))


def load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--other", action="append", default=[],
                    help="another copy of ops/pallas/gdn.py to time beside")
    ap.add_argument("--unpacked", action="store_true")
    ap.add_argument("--calls", type=int, default=20)
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("gdn_scan_table: no TPU here, and a time comes only from one",
              file=sys.stderr)
        return 2
    versions = {"tree": gdn}
    if args.unpacked:
        versions["tree.unpacked"] = load(gdn.__file__, "gdn_unpacked")
        versions["tree.unpacked"]._heads_packed = lambda R, Q: 1
    for i, path in enumerate(args.other):
        versions[path] = load(path, f"gdn_other{i}")
    scans = {name: jax.jit(module.gdn_chunk_scan)
             for name, module in versions.items()}
    twin = jax.jit(gdn.gdn_chunk_scan_xla)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/gdn_scan_table.jsonl", "a") as out:
        for filling in FILLINGS:
            a = arguments(filling)
            want, here = twin(*a), None
            for name, scan in scans.items():    # this tree's is the first
                got = jax.block_until_ready(scan(*a))
                here = got if here is None else here
                t0 = time.perf_counter()
                for _ in range(args.calls):
                    last = scan(*a)
                jax.block_until_ready(last)
                call_us = (time.perf_counter() - t0) / args.calls * 1e6
                line = {"filling": filling, "version": name,
                        "device": jax.devices()[0].device_kind,
                        "kernel_us": round(mosaic_us(scan, a, args.calls), 1),
                        "call_us": round(call_us, 1),
                        "from_twin": [rel(x, y) for x, y in zip(got, want)],
                        "from_tree": [rel(x, y) for x, y in zip(got, here)]}
                print(json.dumps(line), flush=True)
                out.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
