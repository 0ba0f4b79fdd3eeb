"""The K/V page write of the paged pass and of the block step — ONE layer's
write into a pool of real size — timed by itself on the chip at three shapes.

    chiprun --timeout 1500 -- python3 scripts/kv_write_table.py [--groups 16,32,64,128] [--calls 20]

It is the table in PERF.md (PR 62); run it again when the writer, the
compiler or the chip changes. The shapes:

- ``sdar_pass``: cell 15's paged pass — 8 chunk slots of 256 rows (four
  sequences: three slots in a row from an odd position, two from a page's
  start, one short slot, one whole slot; and an empty slot) + 128 idle decode
  rows, 4 KV heads of 128, pages of 128, 6 layers' pool of 5.05 GiB;
- ``sdar_block``: cell 15's block step — 128 sequences' blocks of 4 rows at
  multiples of 4;
- ``mistral_pass``: Mistral-7B's paged pass — 4 slots of 256 + 32 live decode
  rows, 8 KV heads, 16 layers x 792 pages, the chunk sequences on a RING of
  34 pages (the window's 4,096 and a pass) that their positions have gone
  round more than once.

Each is written three ways: ``scatter`` (``ragged_model._kv_page_write``:
one index a row a KV head, K and V — what every pass did before PR 62),
``runs.gN`` (``paged_attention.paged_kv_run_write`` at ``N`` slots a grid
step, ``*`` marks what ``kv_run_group`` picks; single rows still scattered)
and ``windows.gN`` (XLA alone: the groups' ``[N, D]`` windows gathered from
the pool, merged with the runs' rows and scattered back — the same plan,
no kernel). A line gives ``us``, the device microseconds of one layer's
write from a profiler capture (``mosaic_us`` of them inside the Mosaic
call; the run writer's plan, a few small XLA operations that a program
makes once a pass outside its scan over layers, is in the time), ``indices`` the scatter indices the form hands XLA, and ``same``:
whether a pool written so equals the scatter's bit for bit (read on a pool
of the same widths and fewer pages). Lines also go to
``chiprun_out/kv_write_table.jsonl``. No chip, no number: it exits 2.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench.reduce import xplane  # noqa: E402
from deepspeed_tpu.inference.v2.ragged_model import _kv_page_write  # noqa: E402
from deepspeed_tpu.ops.pallas import paged_attention as pa  # noqa: E402

BF16 = jnp.bfloat16

#: name -> layers, pages a layer, KV heads, head width, page, run length,
#: aligned, table width, and the plan: (sequences' runs, single rows)
SHAPES = {
    "sdar_pass": dict(L=6, NB=3449, Hkv=4, D=128, bs=128, n=256, MB=80,
                      aligned=False, singles=128, live_singles=0,
                      # (first position, tokens) a sequence
                      seqs=[(1000, 768), (2048, 512), (300, 200), (5120, 256),
                            (0, 0)]),
    "sdar_block": dict(L=6, NB=3449, Hkv=4, D=128, bs=128, n=4, MB=80,
                       aligned=True, singles=0, live_singles=0,
                       seqs=[(4 * (97 * i % 2000), 4) for i in range(128)]),
    "mistral_pass": dict(L=16, NB=792, Hkv=8, D=128, bs=128, n=256, MB=80,
                         aligned=False, singles=32, live_singles=32,
                         ring=34, seqs=[(9000, 512), (4700, 300)]),
}


def layout(d: dict, pages: int, seed: int = 62):
    """``(tables [R, MB], pos0 [R], count [R], single dest [S])`` of a shape
    over a pool of ``pages`` pages a layer (page 0 is nobody's)."""
    rng = np.random.default_rng(seed)
    n, MB, bs = d["n"], d["MB"], d["bs"]
    free = list(rng.permutation(pages - 1) + 1)
    tables, pos0, count = [], [], []
    for first, tokens in d["seqs"]:
        # pages for the positions written; a ring holds its pages whatever
        # the position, the logical pages going round them
        touched = np.arange(first // bs, (first + max(tokens, 1) - 1) // bs + 1)
        ring = d.get("ring", 0)
        own = np.asarray([free.pop() for _ in range(ring or len(touched))])
        table = np.zeros((MB,), np.int32)
        table[touched] = own[touched % ring] if ring else own
        for at in range(0, max(tokens, 1), n):
            tables.append(table)
            pos0.append(first + at)
            count.append(min(n, tokens - at))
    dest = np.full((d["singles"],), pages * bs, np.int64)
    for i in range(d["live_singles"]):
        dest[i] = int(free.pop()) * bs + int(rng.integers(bs))
    return (np.stack(tables), np.asarray(pos0, np.int32),
            np.asarray(count, np.int32), dest)


def dests(tables, pos0, count, n, bs, sentinel):
    """The row scatter's destinations of the runs (``page * bs + slot``)."""
    i = np.arange(n)[None]
    pos = pos0[:, None] + i
    page = np.take_along_axis(tables, pos // bs, axis=1).astype(np.int64)
    return np.where(i < count[:, None], page * bs + pos % bs,
                    sentinel).reshape(-1)


def windows_write(kv5, k, v, tables, pos0, count, n, group):
    """Candidate 2: the run writer's plan in XLA alone."""
    NB, _, Hkv, bs, D = kv5.shape
    R = tables.shape[0]
    plan = pa.kv_run_plan(tables, pos0, count, n, group, bs)
    run, page, slot, base, lo, hi, off = (a[:plan.steps] for a in plan[3:])
    W = bs // group
    idx = ((page[:, None] * 2 * Hkv + jnp.arange(2 * Hkv)[None]) * W
           + slot[:, None])
    pool3 = kv5.reshape(NB * 2 * Hkv * W, group, D)
    cur = pool3[idx]
    slab = jnp.concatenate(
        [pa._run_rows_with_neighbours(x, R, n, group).reshape(
            R, Hkv, n + 2 * group, D) for x in (k, v)], axis=1)
    new = jax.vmap(lambda r, o: jax.lax.dynamic_slice_in_dim(
        slab[r], o, group, axis=1))(run, off)
    pos = base[:, None] + jnp.arange(group)[None]
    take = (pos >= lo[:, None]) & (pos < hi[:, None])
    merged = jnp.where(take[:, None, :, None], new.astype(kv5.dtype), cur)
    return pool3.at[idx].set(merged).reshape(kv5.shape)


def writers(d: dict, groups):
    """``name -> (write(pool6, k, v, l, tables, pos0, count, dest_runs,
    dest_singles) -> pool6, scatter indices)`` for a shape."""
    L, Hkv, D, bs, n = (d[x] for x in ("L", "Hkv", "D", "bs", "n"))
    R, S = len(layout(d, d["NB"])[1]), d["singles"]

    def flat(fn):
        def write(pool, k, v, l, tables, pos0, count, dr, ds):
            NB = pool.shape[1]
            off = jnp.where(ds >= NB * bs, L * NB * bs, l * NB * bs + ds)
            kvp = pool.reshape(-1, D)
            kvp = fn(kvp, k, v, l, NB, tables, pos0, count, dr, off)
            return kvp.reshape(pool.shape)
        return jax.jit(write, donate_argnums=(0,))

    def scatter(kvp, k, v, l, NB, tables, pos0, count, dr, ds):
        dr = jnp.where(dr >= NB * bs, L * NB * bs, l * NB * bs + dr)
        return _kv_page_write(kvp, k, v, jnp.concatenate([dr, ds]), Hkv, bs)

    def runs(group, form):
        def fn(kvp, k, v, l, NB, tables, pos0, count, dr, ds):
            kv5 = kvp.reshape(L * NB, 2, Hkv, bs, D)
            if form == "runs":
                kv5 = pa.paged_kv_run_write(
                    kv5, k[:R * n], v[:R * n], pa.kv_run_plan(
                        tables, pos0, count, n, group, bs, d["aligned"]),
                    l * NB)
            else:
                kv5 = windows_write(kv5, k[:R * n], v[:R * n],
                                    tables + l * NB, pos0, count, n, group)
            kvp = kv5.reshape(-1, D)
            if S:
                kvp = _kv_page_write(kvp, k[R * n:], v[R * n:], ds, Hkv, bs)
            return kvp
        return fn

    steps = lambda g, a: R * pa._run_steps(n, g, a)
    out = {"scatter": (flat(scatter), (R * n + S) * 2 * Hkv)}
    picked = pa.kv_run_group(jax.ShapeDtypeStruct((1, 2, Hkv, bs, D), BF16),
                             n)
    for g in sorted(set(groups) | {picked}):
        if bs % g or (n >= g and n % g):
            continue
        mark = "*" if g == picked else ""
        out[f"runs.g{g}{mark}"] = (flat(runs(g, "runs")), S * 2 * Hkv)
        out[f"windows.g{g}"] = (flat(runs(g, "windows")),
                                (steps(g, False) + S) * 2 * Hkv)
    return out


def device_us(fn, pool, args, calls: int):
    """``(microseconds a call on the device, of them in Mosaic calls, the
    pool)``, from a capture; the pool is donated call to call."""
    pool = fn(pool, *args)                    # compiled before the capture
    jax.block_until_ready(pool)
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            for _ in range(calls):
                pool = fn(pool, *args)
            jax.block_until_ready(pool)
        path, = glob.glob(os.path.join(tmp, "plugins/profile/*/*.xplane.pb"))
        dev = next(iter(xplane.load(path).devices.values()))
    times = [(xplane.is_mosaic(ev.name), t) for ev, t in dev.self_times()]
    return (sum(t for _, t in times) / calls / 1e3,
            sum(t for m, t in times if m) / calls / 1e3, pool)


def arguments(d: dict, pages: int, seed: int = 62):
    tables, pos0, count, ds = layout(d, pages, seed)
    R, n, Hkv, D = len(pos0), d["n"], d["Hkv"], d["D"]
    key = jax.random.PRNGKey(seed)
    T = R * n + d["singles"]
    k = jax.random.normal(jax.random.fold_in(key, 0), (T, Hkv, D), BF16)
    v = jax.random.normal(jax.random.fold_in(key, 1), (T, Hkv, D), BF16)
    dr = dests(tables, pos0, count, n, d["bs"], pages * d["bs"])
    return (k, v, jnp.int32(d["L"] // 2), jnp.asarray(tables),
            jnp.asarray(pos0), jnp.asarray(count), jnp.asarray(dr, jnp.int32),
            jnp.asarray(ds, jnp.int32)), int(count.sum())


def drawn_pool(d: dict, pages: int, drawn: bool = True):
    """A pool of ``pages`` pages a layer: normal values where the pool is
    compared, one value where it is only timed (what a slot holds costs
    nothing, and 5 GiB drawn would be 10 of float32 first)."""
    shape = (d["L"], pages, 2, d["Hkv"], d["bs"], d["D"])
    if not drawn:
        return jnp.full(shape, 0.5, BF16)
    return jax.jit(lambda: jax.random.normal(
        jax.random.PRNGKey(7), shape, BF16))()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--groups", default="16,32,64,128")
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--check-pages", type=int, default=160)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("kv_write_table: no TPU here, and a time comes only from one",
              file=sys.stderr)
        return 2
    groups = [int(g) for g in args.groups.split(",")]
    os.makedirs("chiprun_out", exist_ok=True)
    lines = []
    for name in args.shapes.split(","):
        d = SHAPES[name]
        forms = writers(d, groups)
        # equality on a pool of fewer pages, every slot compared
        small, _ = arguments(d, args.check_pages)
        want = forms["scatter"][0](drawn_pool(d, args.check_pages), *small)
        same = {}
        for form, (fn, _) in forms.items():
            try:
                got = fn(drawn_pool(d, args.check_pages), *small)
                same[form] = bool(jnp.array_equal(got, want))
            except Exception as e:          # a form the compiler refuses
                same[form] = f"failed: {str(e)[:200]}"
        del want
        real, run_rows = arguments(d, d["NB"])
        pool = drawn_pool(d, d["NB"], drawn=False)
        for form, (fn, indices) in forms.items():
            line = dict(shape=name, form=form, indices=indices,
                        run_rows=run_rows, same=same[form])
            if same[form] in (True, False):
                us, mosaic, pool = device_us(fn, pool, real, args.calls)
                line.update(us=round(us, 1), mosaic_us=round(mosaic, 1))
            line.update(device=dev.device_kind, jax=jax.__version__)
            print(json.dumps(line), flush=True)
            lines.append(line)
        del pool
    with open("chiprun_out/kv_write_table.jsonl", "w") as f:
        f.writelines(json.dumps(x) + "\n" for x in lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
