"""The grouped GEMM of the serving path's MoE layers, timed by itself on the
chip: XLA's kernel for ``jax.lax.ragged_dot`` against
``ops/pallas/grouped_matmul.py`` at four models' expert shapes, by the share
of a layer's groups that hold rows and by row count.

    chiprun --timeout 1500 -- python3 scripts/moe_grouped_table.py [--shapes trinity,joyai,mixtral]

It is the table in PERF.md (PR 34) that fixed ``ragged_model._moe_ffn``'s
choice of kernel; run it again when the kernel, the compiler or the chip
changes. Each line is one (shape, product, rows, share of groups touched,
kernel): microseconds a call — host clock around a program of ``calls`` calls
walking the stack's layers, the device never idle inside it — and GB/s over
the bytes of the touched matrices and over the bytes of the layer's whole
table. Both kernels get what ``_moe_ffn`` gives them: the whole stack
``[L*E, K, N]`` in place and layer ``l``'s ``E`` sizes; XLA's gets them at
offset ``l*E`` of an ``L*E`` vector, as the XLA branch of ``gg`` does. No chip,
no number: it exits 2.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from deepspeed_tpu.ops.pallas.grouped_matmul import (grouped_matmul,  # noqa: E402
                                                     plan_visits, row_tile)

#: layers, experts a layer (held ones for joyai), hidden, expert width, top-k,
#: the router's width (what an assignment is drawn over)
SHAPES = {
    "trinity": dict(L=4, E=128, hid=2048, ffn=1024, top_k=8, routed=128),
    "joyai": dict(L=39, E=16, hid=2048, ffn=768, top_k=8, routed=256),
    "mixtral": dict(L=3, E=8, hid=4096, ffn=14336, top_k=2, routed=8),
    # 64 held of 128, two matrices an expert, a width of 14.5 lane tiles
    # (--tokens 128,2048: 384 and 6,144 rows on the held experts); and the
    # same zero-padded to 15 tiles, for what the pad would buy
    "nemotron": dict(L=7, E=64, hid=2688, ffn=1856, top_k=6, routed=128),
    "nemotron_pad1920": dict(L=7, E=64, hid=2688, ffn=1920, top_k=6,
                             routed=128),
}
HBM_GBS = 819.0


def group_sizes(rng, E: int, touched: int, rows: int) -> np.ndarray:
    """``rows`` rows over ``touched`` of ``E`` groups, each at least one."""
    sizes = np.zeros(E, np.int64)
    on = rng.choice(E, size=touched, replace=False)
    sizes[on] = 1 + rng.multinomial(rows - touched, np.ones(touched) / touched)
    return sizes.astype(np.int32)


def time_program(fn, args, calls: int, repeats: int = 5):
    fn(*args).block_until_ready()            # compile, warm
    fn(*args).block_until_ready()
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        times.append(time.perf_counter() - t0)
    return statistics.median(times) / calls * 1e6, min(times) / calls * 1e6


def programs(L: int, E: int, m: int, calls: int, tm: int, tiles):
    """name -> jitted ``(lhs, stack, sizes) -> scalar`` running ``calls``
    grouped products, layer ``i % L`` in call ``i``."""

    def xla(lhs, groups, sizes, l):
        full = jax.lax.dynamic_update_slice(
            jnp.zeros(groups.shape[0], jnp.int32), sizes, (l * E,))
        return jax.lax.ragged_dot(lhs, groups, full)

    def pallas(lhs, groups, sizes, l):
        # sizes made to depend on the call, as a layer's are, so that the
        # plan is built in every call and not hoisted out of the loop
        return grouped_matmul(lhs, groups,
                              plan_visits(sizes + l // 65536, m, tm), l,
                              tiles=tiles)

    def plan_only(lhs, groups, sizes, l):
        v = plan_visits(sizes + l // 65536, m, tm)
        return (v.offsets.sum() + v.group.sum() + v.tile.sum()
                + v.count.sum()).reshape(1, 1)

    def loop(one):
        def run(lhs, stack, sizes):
            groups = stack.reshape((-1,) + stack.shape[-2:])

            def body(i, acc):
                out = one(lhs, groups, sizes, i % L)
                return acc + out[0, 0].astype(jnp.float32)
            return jax.lax.fori_loop(0, calls, body, jnp.float32(0))
        return jax.jit(run)

    return {"xla": loop(xla), "pallas": loop(pallas), "plan": loop(plan_only),
            "one_xla": jax.jit(lambda a, s, g: xla(
                a, s.reshape((-1,) + s.shape[-2:]), g, L - 1)),
            "one_pallas": jax.jit(lambda a, s, g: pallas(
                a, s.reshape((-1,) + s.shape[-2:]), g, L - 1))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="trinity,joyai,mixtral")
    ap.add_argument("--shares", default="100,75,50,25")
    ap.add_argument("--tiles", default="",
                    help="extra rhs tilings to try, 'tk x tn' separated by "
                         "commas (e.g. 2048x512,1024x1024); the kernel's own "
                         "choice is always run")
    ap.add_argument("--row-tiles", default="",
                    help="extra row tiles to try (e.g. 16,64)")
    ap.add_argument("--tokens", default="32,256,1024",
                    help="tokens a call (rows = tokens x top-k); the shares "
                         "are walked at the first count, the others have "
                         "every group touched")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/moe_grouped_table.jsonl")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    out = open(args.out, "a")
    rng = np.random.default_rng(args.seed)
    extra_tiles = [tuple(int(x) for x in t.split("x"))
                   for t in args.tiles.split(",") if t]
    extra_tm = [int(t) for t in args.row_tiles.split(",") if t]

    def emit(**line):
        line["device"] = dev.device_kind
        print(" ".join(f"{k}={v}" for k, v in line.items()
                       if k not in ("device", "K", "N", "groups", "rows")),
              flush=True)
        out.write(json.dumps(line) + "\n")
        out.flush()

    for name in args.shapes.split(","):
        s = SHAPES[name]
        L, E = s["L"], s["E"]
        for product, (K, N) in (("up", (s["hid"], s["ffn"])),
                                ("down", (s["ffn"], s["hid"]))):
            stack = jax.random.normal(jax.random.PRNGKey(args.seed),
                                      (L, E, K, N), jnp.bfloat16) * 0.02
            stack.block_until_ready()
            matrix = K * N * 2
            for nth, tokens in enumerate(int(t) for t in
                                         args.tokens.split(",")):
                m = tokens * s["top_k"]
                # rows on this table's groups: all of them, or the held share
                on = max(E, m * E // s["routed"])
                lhs = jax.random.normal(jax.random.PRNGKey(1), (m, K),
                                        jnp.bfloat16)
                # enough calls that the program's dispatch (0.5 ms) is little
                calls = max(L, (48 if m <= 1024 else 12) // L * L)
                shares = [int(x) for x in args.shares.split(",")]
                if nth:
                    shares = shares[:1]
                for share in shares:
                    touched = max(1, E * share // 100)
                    sizes = jnp.asarray(group_sizes(rng, E, touched,
                                                    max(on, touched)))
                    variants = [(row_tile(m), None)]
                    variants += [(variants[0][0], t) for t in extra_tiles
                                 if K % t[0] == 0 and N % t[1] == 0]
                    variants += [(t, None) for t in extra_tm if t <= m]
                    base = dict(shape=name, product=product, tokens=tokens,
                                rows=m, rows_on_groups=int(sizes.sum()),
                                K=K, N=N, groups=E, touched=touched)
                    ref = None
                    for i, (tm, tiles) in enumerate(variants):
                        mp = -(-m // tm) * tm
                        lhs_p = jnp.pad(lhs, ((0, mp - m), (0, 0)))
                        progs = programs(L, E, mp, calls, tm, tiles)
                        todo = ["xla", "plan", "pallas"] if i == 0 else ["pallas"]
                        for kernel in todo:
                            try:
                                us, best = time_program(
                                    progs[kernel], (lhs_p, stack, sizes), calls)
                            except Exception as e:  # a tiling Mosaic refuses
                                emit(**base, kernel=kernel, tm=tm, tiles=tiles,
                                     error=str(e)[:300])
                                continue
                            emit(**base, kernel=kernel, tm=tm, tiles=tiles,
                                 us_call=round(us, 2), us_call_min=round(best, 2),
                                 gbs_touched=round(touched * matrix / us / 1e3, 1),
                                 gbs_table=round(E * matrix / us / 1e3, 1),
                                 hbm_share_touched=round(
                                     touched * matrix / us / 1e3 / HBM_GBS, 3))
                        n = int(sizes.sum())
                        if ref is None:
                            ref = np.asarray(progs["one_xla"](
                                lhs_p, stack, sizes)[:n], np.float32)
                        try:
                            got = np.asarray(progs["one_pallas"](
                                lhs_p, stack, sizes)[:n], np.float32)
                        except Exception as e:  # a shape Mosaic refuses
                            emit(**base, kernel="check", tm=tm, tiles=tiles,
                                 error=str(e)[:300])
                            continue
                        emit(**base, kernel="check", tm=tm, tiles=tiles,
                             max_abs_diff=float(np.abs(got - ref).max()),
                             ref_abs_max=float(np.abs(ref).max()))
            del stack
    return 0


if __name__ == "__main__":
    sys.exit(main())
