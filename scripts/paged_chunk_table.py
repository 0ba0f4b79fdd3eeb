"""``paged_chunk_attention_batched`` timed by itself on the chip at the widths
of three cells, by how full the block tables are.

    chiprun --timeout 1500 -- python3 scripts/paged_chunk_table.py [--other path/to/paged_attention.py ...]

It is the table in PERF.md (PR 49); run it again when the kernel, the
compiler or the chip changes. Shapes: ``qwen3next`` (cell 11: 8 slots of 256
rows, 16 query heads over 2 KV heads of 256, 272-page tables), ``mistral``
(cells 1 and 6: 4 slots, 32 over 8 heads of 128, window 4,096, 40 pages) and
``trinity_full`` (cell 5's full layer: 4 slots, 32 over 4 heads of 128, 208
pages); pages of 128 tokens, bfloat16. Fillings: a nominal context of 1k, 5k
or 30k tokens (no more than the table holds), ragged as the cell's passes —
each slot another length around it, ``q_start`` inside a page, the last slot
but one part full, the last one empty. A line gives, for one version of the
module, the microseconds of the Mosaic call a run (device time from a
profiler capture), that time's share of the MXU's one-pass roof for the keys
the live rows actually see (``4 H D`` operations a row and key), and how far
the output is from a float32 ``jnp`` reference and from the same reference
with ``p`` rounded to bfloat16 before ``P V`` (root mean square, relative),
and from the first version's output (``--pages 1`` first and a parent after
it: 0.0 where both issue the same products on the same blocks).
``--other`` names further copies of ``ops/pallas/paged_attention.py`` to time
beside this tree's (a parent's, unpacked from ``git archive``); ``--pages``
also times this tree's at other page counts a grid step than it picks.
``--probe`` answers what a float32 product costs and keeps inside a kernel:
the time of ``[1024, 128] x [128, 256]`` with float32 operands at the
default and the highest precision, with bfloat16 ones, and with a float32
``p`` rounded or split in three in the kernel, and each version's distance
from the two references with q held in float32 (so that the output's own
rounding does not hide ``p``'s). Lines also go to
``chiprun_out/paged_chunk_table.jsonl``. No chip, no number: it exits 2.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402

from deepspeed_tpu.ops.pallas import paged_attention  # noqa: E402
from gdn_scan_table import load, mosaic_us, rel  # noqa: E402  (beside this file)

BS = 128
SHAPES = {
    "qwen3next": dict(slots=8, heads=16, kv_heads=2, dim=256, pages=272,
                      window=None),
    "mistral": dict(slots=4, heads=32, kv_heads=8, dim=128, pages=40,
                    window=4096),
    "trinity_full": dict(slots=4, heads=32, kv_heads=4, dim=128, pages=208,
                         window=None),
}
ROWS = 256
CONTEXTS = (1024, 5120, 30720)
#: a slot's context as a share of the nominal one, and the rows it holds
RAGGED = ((1.0, 256), (0.6, 256), (1.4, 256), (0.9, 256), (1.2, 256),
          (0.75, 256), (1.1, 200), (0.0, 0))


def arguments(shape: dict, context: int, seed: int = 49):
    """One call's arguments: the last ``slots`` entries of ``RAGGED``, every
    slot's pages its own, drawn from the whole pool."""
    S, H, Hkv, D, MB = (shape[k] for k in ("slots", "heads", "kv_heads",
                                            "dim", "pages"))
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    NB = S * MB + 1
    kv = jax.random.normal(key, (NB, 2, Hkv, BS, D), jnp.bfloat16)
    q = jax.random.normal(jax.random.fold_in(key, 1), (S, ROWS, H, D),
                          jnp.bfloat16)
    tables = (rng.permutation(NB - 1)[:S * MB] + 1).reshape(S, MB)
    ctx, q0 = [], []
    for share, rows in RAGGED[-S:]:
        c = 0 if rows == 0 else max(rows, min(int(context * share) + 37,
                                              MB * BS))
        ctx.append(c)
        q0.append(c - rows if rows else 0)
    return (q, kv, jnp.asarray(tables, jnp.int32),
            jnp.asarray(q0, jnp.int32), jnp.asarray(ctx, jnp.int32))


def visible_keys(q0, ctx, window) -> int:
    """Keys the live rows see, summed over rows (a row past ``ctx`` is
    computed and ignored: not counted)."""
    total = 0
    for start, end in zip(np.asarray(q0), np.asarray(ctx)):
        pos = np.arange(start, min(start + ROWS, end)) + 1
        total += int(np.sum(pos if window is None
                            else np.minimum(pos, window)))
    return total


@jax.jit
def _gather(kv, table):
    pages = kv[table]                               # [MB, 2, Hkv, bs, D]
    seq = jnp.moveaxis(pages, 1, 0)                 # [2, MB, Hkv, bs, D]
    seq = jnp.moveaxis(seq, 2, 1).reshape(2, pages.shape[2], -1,
                                          pages.shape[4])
    return seq.astype(jnp.float32)                  # [2, Hkv, MB*bs, D]


@jax.jit
def _slot_reference(q, k, v, q0, ctx, window):
    """One slot in float32 at the highest precision: the output, and the
    output with ``p`` (against the row's final maximum) rounded to bfloat16
    before ``P V``. ``window`` 0 is none."""
    C, H, D = q.shape
    G = H // k.shape[0]
    qg = q.astype(jnp.float32).reshape(C, k.shape[0], G, D)
    with jax.default_matmul_precision("highest"):
        sc = jnp.einsum("chgd,hkd->hgck", qg, k) * D ** -0.5
        q_pos = q0 + jnp.arange(C)[:, None]
        k_pos = jnp.arange(k.shape[1])[None, :]
        mask = (k_pos <= q_pos) & (k_pos < ctx)
        mask = mask & ((window == 0) | (k_pos > q_pos - window))
        sc = jnp.where(mask, sc, -1e30)
        p = jnp.where(mask, jnp.exp(sc - sc.max(-1, keepdims=True)), 0.0)
        l = jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
        outs = [jnp.einsum("hgck,hkd->chgd", x, v) / jnp.moveaxis(l, 2, 0)
                for x in (p, p.astype(jnp.bfloat16).astype(jnp.float32))]
    return [o.reshape(C, H, D) for o in outs]


def references(args, window):
    q, kv, tables, q0, ctx = args
    exact, rounded = [], []
    for s in range(q.shape[0]):
        k, v = _gather(kv, tables[s])
        a, b = _slot_reference(q[s], k, v, q0[s], ctx[s], window or 0)
        exact.append(np.asarray(a))
        rounded.append(np.asarray(b))
    return np.stack(exact), np.stack(rounded)


def held_to(module, pages):
    """``module``'s kernel with a grid step held to ``pages`` pages while it
    is traced (None: what its shapes pick)."""
    def call(*args, **kw):
        picker = module._pick_chunk_pages
        if pages is not None:
            module._pick_chunk_pages = lambda *a, **k: pages
        try:
            return module.paged_chunk_attention_batched(*args, **kw)
        finally:
            module._pick_chunk_pages = picker
    return call if pages is not None else module.paged_chunk_attention_batched


PRODUCTS = ("float32", "float32_highest", "bfloat16", "float32_rounded",
            "float32_split3")


def product_us(kind: str, calls: int) -> float:
    """``[1024, 128] x [128, 256]`` (a KV head's ``p`` of one page times its
    V at cell 11's widths), 32 of them a grid step on operands that are in
    VMEM (4 ``p`` x 8 ``V``, every pair another), 64 steps: microseconds a
    product. ``float32`` is what the parent's kernel issues (both operands
    widened, the default precision); ``float32_rounded`` rounds the float32
    ``p`` to bfloat16 in the kernel and ``float32_split3`` splits it in three
    bfloat16 parts (three one-pass products: the float32 result with a
    bfloat16 V)."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    wide = kind in ("float32", "float32_highest")
    precision = jax.lax.Precision.HIGHEST if kind == "float32_highest" \
        else None

    def dot(a, b):
        return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                   precision=precision,
                                   preferred_element_type=f32)

    def kernel(a_ref, b_ref, o_ref):
        def one(i, acc):
            a, b = a_ref[0, i % 4], b_ref[i % 8]
            if kind == "float32_rounded":
                return acc + dot(a.astype(bf16), b)
            if kind == "float32_split3":
                hi = a.astype(bf16)
                rest = a - hi.astype(f32)
                mid = rest.astype(bf16)
                lo = (rest - mid.astype(f32)).astype(bf16)
                return acc + (dot(lo, b) + dot(mid, b) + dot(hi, b))
            return acc + dot(a, b)

        o_ref[0] = jax.lax.fori_loop(0, 32, one,
                                     jnp.zeros((1024, 256), f32))

    steps = 64
    call = jax.jit(pl.pallas_call(
        kernel, grid=(steps,),
        in_specs=[pl.BlockSpec((1, 4, 1024, 128), lambda i: (i, 0, 0, 0)),
                  pl.BlockSpec((8, 128, 256), lambda i: (0, 0, 0))],
        out_specs=pl.BlockSpec((1, 1024, 256), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((steps, 1024, 256), f32)))
    key = jax.random.PRNGKey(0)
    a = jax.random.uniform(key, (steps, 4, 1024, 128), f32)
    b = jax.random.normal(key, (8, 128, 256), f32).astype(bf16)
    if kind == "bfloat16":
        a = a.astype(bf16)
    operands = (a, b.astype(f32) if wide else b)
    jax.block_until_ready(call(*operands))           # compiled before the capture
    return mosaic_us(call, operands, calls) / steps / 32


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--other", action="append", default=[],
                    help="another copy of ops/pallas/paged_attention.py")
    ap.add_argument("--pages", default="",
                    help="page counts a step to time beside the picked one")
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--contexts", default=",".join(map(str, CONTEXTS)))
    ap.add_argument("--probe", action="store_true")
    ap.add_argument("--calls", type=int, default=10)
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("paged_chunk_table: no TPU here, and a time comes only from "
              "one", file=sys.stderr)
        return 2
    with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
            __file__))), "chipbench", "peaks.json")) as f:
        peak = json.load(f)[jax.devices()[0].device_kind]["bf16_flops_per_s"]
    versions = {f"tree.pages{n}": (paged_attention, int(n))
                for n in filter(None, args.pages.split(","))}
    versions["tree"] = (paged_attention, None)
    for i, path in enumerate(args.other):
        versions[path] = (load(path, f"paged_other{i}"), None)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/paged_chunk_table.jsonl", "a") as out:
        def emit(line):
            line["device"] = jax.devices()[0].device_kind
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")

        if args.probe:
            for kind in PRODUCTS:
                emit({"probe": "product_1024x128x256", "operands": kind,
                      "us": round(product_us(kind, args.calls), 3)})
        for name in args.shapes.split(","):
            shape = SHAPES[name]
            for context in map(int, args.contexts.split(",")):
                a = arguments(shape, context)
                exact, rounded = references(a, shape["window"])
                keys = visible_keys(a[3], a[4], shape["window"])
                roof_us = 4 * shape["heads"] * shape["dim"] * keys / peak * 1e6
                first = None
                for version, (module, pages) in versions.items():
                    fn = jax.jit(functools.partial(
                        held_to(module, pages), window=shape["window"]))
                    got = np.asarray(fn(*a).astype(jnp.float32))
                    us = mosaic_us(fn, a, args.calls)
                    line = {"shape": name, "context": context,
                            "ctx": np.asarray(a[4]).tolist(),
                            "version": version, "kernel_us": round(us, 1),
                            "mxu_roof_us": round(roof_us, 1),
                            "mxu_roof_share": round(100 * roof_us / us, 2),
                            "from_float32": rel(got, exact),
                            "from_p_rounded": rel(got, rounded)}
                    if first is not None:
                        # 0.0 where a version issues the first one's products
                        line["from_first"] = rel(got, first)
                    first = got if first is None else first
                    if args.probe:
                        # q held in float32: the parent's arithmetic on the
                        # same values, and an output that is not rounded
                        # (where the wider blocks still fit the kernel)
                        wide = (a[0].astype(jnp.float32),) + a[1:]
                        try:
                            got32 = np.asarray(fn(*wide))
                        except jax.errors.JaxRuntimeError:
                            got32 = None
                        else:
                            line["q_float32_from_float32"] = rel(got32, exact)
                            line["q_float32_from_p_rounded"] = rel(got32,
                                                                   rounded)
                    emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
