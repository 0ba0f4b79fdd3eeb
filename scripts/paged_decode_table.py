"""``paged_decode_attention_sidebuf`` timed by itself on the chip at the
shapes the serving cells' decode steps hand it, by what the rows hold.

    chiprun --timeout 1500 -- python3 scripts/paged_decode_table.py [--parts] [--other path/to/paged_attention.py ...]

It is the table in PERF.md (PR 51); run it again when the kernel, the
compiler or the chip changes. Shapes (rows, query / KV heads, head width,
block-table width, window; pages of 128 tokens, bfloat16, the side slab as
the decode step keeps it — ``[L, S, lcm(Hkv, 8), D]`` with a traced layer
index and ``j = 0``): ``zaya`` (cell 12), ``qwen3next`` (cell 11),
``nemotron`` (cell 10), ``jamba`` (cell 7), ``trinity_full`` and
``trinity_window`` (cell 5's full layer and its 2,048 window), ``mistral``
(cells 1 and 6: 20 of 32 rows live), ``mixtral`` (cell 3) and ``granite``
(cell 9). A row's context is drawn as its cell's traffic draws it — a
lognormal prompt plus a uniform share of an output, clipped to the table —
from a fixed seed; rows past ``live`` hold nothing. A line gives, for one
version of the module, the microseconds of the Mosaic call a run (device
time from a profiler capture), the bytes the live pages hold (every page that
holds a key its row sees, whole), that many bytes' share of the HBM rate over
the call's time, and how far the output is from a float64 reference on four
rows (the shortest and the longest live one, a middle one, the last row) and
from the first version's output. ``--other`` names further copies of
``ops/pallas/paged_attention.py`` to time beside this tree's (a parent's,
unpacked from ``git archive``); ``--pages`` also times this tree's at other
page counts a group than it picks.

``--parts`` prices a version part by part at the first shape named (cell
12's unless ``--shapes`` says otherwise): ``empty`` (every row a context of
no key: what the steps that hold none cost), ``live21`` and ``live31`` (every
row 21 or 31 whole pages: is the time by the page or by the chunk?),
``all31.pagesN`` (31 pages a row, the kernel held to N pages a chunk or a
group: the control per page), ``resident`` (the copies neither started nor
awaited: products, masks and ``exp`` alone, over whatever the buffers hold)
and ``stream`` (the copies alone: the online-softmax update removed, so the
products feed nothing and go). Lines also go to
``chiprun_out/paged_decode_table.jsonl``. No chip, no number: it exits 2.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from deepspeed_tpu.ops.pallas import paged_attention  # noqa: E402
from gdn_scan_table import load, mosaic_us, rel  # noqa: E402  (beside this file)

BS = 128
#: prompt (lognormal median, min, max) and the longest output, as the cell's
#: traffic file has them; ``live`` rows of ``rows`` hold a sequence
SHAPES = {
    "zaya": dict(rows=64, live=64, heads=8, kv_heads=2, dim=128, pages=96,
                 window=None, prompt=(768, 64, 8192), output=3072),
    "qwen3next": dict(rows=64, live=56, heads=16, kv_heads=2, dim=256,
                      pages=272, window=None, prompt=(4096, 512, 32768),
                      output=1024),
    "nemotron": dict(rows=128, live=126, heads=32, kv_heads=2, dim=128,
                     pages=96, window=None, prompt=(768, 64, 8192),
                     output=3072),
    "jamba": dict(rows=128, live=127, heads=20, kv_heads=1, dim=128, pages=96,
                  window=None, prompt=(768, 64, 8192), output=3072),
    "trinity_full": dict(rows=32, live=30, heads=32, kv_heads=4, dim=128,
                         pages=208, window=None, prompt=(4096, 512, 24576),
                         output=1024),
    "trinity_window": dict(rows=32, live=30, heads=32, kv_heads=4, dim=128,
                           pages=208, window=2048, prompt=(4096, 512, 24576),
                           output=1024),
    "mistral": dict(rows=32, live=20, heads=32, kv_heads=8, dim=128, pages=40,
                    window=4096, prompt=(512, 64, 3072), output=400),
    "mixtral": dict(rows=32, live=31, heads=32, kv_heads=8, dim=128, pages=32,
                    window=None, prompt=(576, 128, 1024), output=512),
    "granite": dict(rows=64, live=63, heads=32, kv_heads=8, dim=128, pages=80,
                    window=None, prompt=(1536, 256, 8192), output=1536),
}
PARTS = ("empty", "live21", "live31", "all31.pages4", "all31.pages8",
         "all31.pages16", "all31.pages31", "resident", "stream")
LAYERS = 2


def contexts(shape: dict, seed: int = 51) -> np.ndarray:
    """The rows' prefixes: a prompt as the traffic draws it plus what a
    request in flight has decoded of its output, whole tables at the most."""
    rng = np.random.default_rng(seed)
    median, lo, hi = shape["prompt"]
    prompt = np.clip(rng.lognormal(math.log(median), 1.0, shape["rows"]),
                     lo, hi)
    # a request is in flight for as long as its output is: of two drawn,
    # the longer is the likelier to be met
    longest = rng.uniform(shape["output"] / 6, shape["output"],
                          (2, shape["rows"])).max(0)
    done = rng.uniform(0, 1, shape["rows"]) * longest
    ctx = np.minimum(prompt + done, shape["pages"] * BS - 1).astype(np.int64)
    ctx[shape["live"]:] = 0
    return ctx


def live_pages(ctx: np.ndarray, window) -> np.ndarray:
    """Pages a row reads: those that hold a key of ``[lo, prefix)``."""
    lo = np.zeros_like(ctx) if window is None \
        else np.maximum(ctx + 1 - window, 0)
    return np.where(ctx > 0, -(-ctx // BS) - lo // BS, 0)


def arguments(shape: dict, ctx: np.ndarray, seed: int = 51):
    """One call's arguments. A row's pages are its own, drawn from a pool
    that holds the live pages and no more; a table's other entries are 0."""
    S, H, Hkv, D, MB = (shape[k] for k in ("rows", "heads", "kv_heads",
                                            "dim", "pages"))
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    held = -(-ctx // BS)
    NB = int(held.sum()) + 1
    kv = jax.random.normal(key, (NB, 2, Hkv, BS, D), jnp.bfloat16)
    order = rng.permutation(NB - 1) + 1
    tables = np.zeros((S, MB), np.int32)
    at = 0
    for s in range(S):
        tables[s, :held[s]] = order[at:at + held[s]]
        at += held[s]
    rows = math.lcm(Hkv, 8)
    q = jax.random.normal(jax.random.fold_in(key, 1), (S, H, D), jnp.bfloat16)
    side_k = jax.random.normal(jax.random.fold_in(key, 2),
                               (LAYERS, S, rows, D), jnp.bfloat16)
    side_v = jax.random.normal(jax.random.fold_in(key, 3),
                               (LAYERS, S, rows, D), jnp.bfloat16)
    return (q, kv, jnp.asarray(tables), jnp.asarray(ctx, jnp.int32),
            side_k, side_v, jnp.int32(0), jnp.int32(LAYERS - 1))


def row_reference(args, s: int, window) -> np.ndarray:
    """Row ``s`` in float64 on the host: its pages' keys and step 0 of its
    slab, one softmax."""
    q, kv, tables, ctx, side_k, side_v, _, layer = args
    n, Hkv = int(ctx[s]), kv.shape[2]
    H, D = q.shape[1:]
    G = H // Hkv
    pages = np.asarray(kv[tables[s, :max(-(-n // BS), 1)]].astype(
        jnp.float32), np.float64)                   # [n_pages, 2, Hkv, bs, D]
    lo = 0 if window is None else max(n + 1 - window, 0)
    out = np.zeros((H, D))
    for h in range(Hkv):
        k = pages[:, 0, h].reshape(-1, D)[lo:n]
        v = pages[:, 1, h].reshape(-1, D)[lo:n]
        k = np.concatenate([k, np.asarray(
            side_k[layer, s, h:h + 1].astype(jnp.float32), np.float64)])
        v = np.concatenate([v, np.asarray(
            side_v[layer, s, h:h + 1].astype(jnp.float32), np.float64)])
        qh = np.asarray(q[s, h * G:(h + 1) * G].astype(jnp.float32),
                        np.float64)
        sc = qh @ k.T * D ** -0.5
        p = np.exp(sc - sc.max(-1, keepdims=True))
        out[h * G:(h + 1) * G] = p @ v / p.sum(-1, keepdims=True)
    return out


class _NoCopy:
    """``pltpu`` with copies that are neither started nor awaited."""

    def __init__(self, pltpu):
        self._pltpu = pltpu

    def __getattr__(self, name):
        return getattr(self._pltpu, name)

    def make_async_copy(self, *_):
        class Copy:
            start = wait = staticmethod(lambda: None)
        return Copy()


#: the module's names a part replaces while the call is traced; a name the
#: version does not have is left alone
def _replacements(module, part: str) -> dict:
    if part == "resident":
        return {"pltpu": _NoCopy(module.pltpu)}
    if part == "stream":
        return {"_flash_update": lambda *a, **k: None,
                "_decode_update": lambda *a, **k: None}
    if ".pages" in part:
        n = int(part.split(".pages")[1])
        return {"_pick_pages_per_chunk": lambda *a, **k: n,
                "_pick_decode_pages": lambda *a, **k: n}
    return {}


@contextlib.contextmanager
def replaced(module, names: dict):
    kept = {k: getattr(module, k) for k in names if hasattr(module, k)}
    for k in kept:
        setattr(module, k, names[k])
    try:
        yield
    finally:
        for k, v in kept.items():
            setattr(module, k, v)


def _call(kernel, window, *a):
    return kernel(*a[:7], layer_idx=a[7], window=window)


def part_contexts(shape: dict, part: str) -> np.ndarray:
    pages = {"empty": 0, "live21": 21}.get(part, 31)
    return np.full(shape["rows"], pages * BS, np.int64)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--other", action="append", default=[],
                    help="another copy of ops/pallas/paged_attention.py")
    ap.add_argument("--pages", default="",
                    help="page counts a group to time beside the picked one")
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--parts", action="store_true",
                    help="also price each version part by part at the first "
                         "shape")
    ap.add_argument("--calls", type=int, default=20)
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("paged_decode_table: no TPU here, and a time comes only from "
              "one", file=sys.stderr)
        return 2
    with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
            __file__))), "chipbench", "peaks.json")) as f:
        rate = json.load(f)[jax.devices()[0].device_kind]["hbm_bytes_per_s"]
    versions = {"tree": (paged_attention, "")}
    for n in filter(None, args.pages.split(",")):
        versions[f"tree.pages{n}"] = (paged_attention, f".pages{n}")
    for i, path in enumerate(args.other):
        versions[path] = (load(path, f"paged_other{i}"), "")
    names = args.shapes.split(",")
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/paged_decode_table.jsonl", "a") as out:
        def emit(line):
            line["device"] = jax.devices()[0].device_kind
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")

        def measure(name, part, ctx, compare):
            shape = SHAPES[name]
            a = arguments(shape, ctx)
            page_bytes = 2 * shape["kv_heads"] * BS * shape["dim"] * 2
            pages = live_pages(ctx, shape["window"])
            nbytes = int(pages.sum()) * page_bytes
            first = None
            for version, (module, held) in versions.items():
                fn = jax.jit(functools.partial(
                    _call, module.paged_decode_attention_sidebuf,
                    shape["window"]))
                try:
                    with replaced(module, _replacements(module, part + held)):
                        # traced and compiled here
                        got = np.asarray(fn(*a).astype(jnp.float32))
                except jax.errors.JaxRuntimeError as e:
                    # (more pages a group than VMEM holds, when held to it)
                    emit({"shape": name, "part": part, "version": version,
                          "error": str(e).split("\n")[0][:200]})
                    continue
                us = mosaic_us(fn, a, args.calls)
                line = {"shape": name, "part": part, "version": version,
                        "rows": shape["rows"],
                        "live_rows": int((ctx > 0).sum()),
                        "pages_mean": round(float(pages[ctx > 0].mean()), 1)
                        if (ctx > 0).any() else 0.0,
                        "pages_max": int(pages.max()),
                        "kernel_us": round(us, 1), "live_bytes": nbytes,
                        "hbm_floor_us": round(nbytes / rate * 1e6, 1),
                        "hbm_rate_share": round(100 * nbytes / rate
                                                / (us * 1e-6), 2)}
                if compare:
                    live = np.flatnonzero(ctx > 0)
                    by_len = live[np.argsort(ctx[live])]
                    picked = sorted({int(by_len[0]), int(by_len[-1]),
                                     int(by_len[len(by_len) // 2]),
                                     shape["rows"] - 1})
                    want = np.stack([row_reference(a, s, shape["window"])
                                     for s in picked])
                    line["from_float64"] = rel(got[picked], want)
                    if first is not None:
                        line["from_first"] = rel(got, first)
                    first = got if first is None else first
                emit(line)

        for name in names:
            measure(name, "cell", contexts(SHAPES[name]), compare=True)
        if args.parts:
            for part in PARTS:
                measure(names[0], part, part_contexts(SHAPES[names[0]], part),
                        compare=False)
    return 0


if __name__ == "__main__":
    sys.exit(main())
