"""``ragged_model._moe_ffn`` on a held share of experts, timed by itself on
the chip, part by part: the path that sorts and combines every choice (what
the decode steps, and every model whose share gives no bound, run) against
the compact path of the prefill passes (docs/SERVING.md "Held experts").

    chiprun --timeout 1500 -- python3 scripts/moe_held_table.py [--shapes qwen3_next.pass,...]

It is the table in PERF.md (PR 53); run it again when ``_moe_ffn``, the
compiler or the chip changes. Shapes: Qwen3-Next's pass (2,112 rows x top-10
of 64 held / 512 routed, experts 2,048 -> 512 -> 2,048) and decode step (64
rows), JoyAI's pass (1,056 x top-8 of 16 / 256, 768 wide, the sigmoid
router) and decode step (32 rows); bfloat16, the routers drawn at random (a
router that spreads its choices). ``overflow`` hands the same shapes a
routing whose every choice lands on a held expert (the router left out):
ceil(rows x top-k / bound) turns.

A line is one (shape, routing, version): device microseconds a call from a
profiler capture — the whole program's, and by part, every device operation
put to the scope its ``op_name`` carries (``router``, ``sort``, ``experts``,
``combine``) and, inside a scope, to what it is: the sort itself, a gather,
a Mosaic call (the three grouped products), the rest (plan and counts under
``sort``; activation, mask and scale under ``experts``; the one-hot product
or the inverse gather and sum under ``combine``). Lines also go to
``chiprun_out/moe_held_table.jsonl``, with every operation over 1% of the
call beside them. No chip, no number: it exits 2.
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

from chipbench.reduce import hlo_names, named, xplane  # noqa: E402
from deepspeed_tpu.inference.v2 import ragged_model as rm  # noqa: E402

SHAPES = {
    "qwen3_next.pass": dict(rows=2112, top_k=10, held=64, routed=512,
                            hid=2048, ffn=512, routing={}),
    "qwen3_next.decode": dict(rows=64, top_k=10, held=64, routed=512,
                              hid=2048, ffn=512, routing={}),
    "joyai.pass": dict(rows=1056, top_k=8, held=16, routed=256, hid=2048,
                       ffn=768, routing={"score_func": "sigmoid",
                                         "route_norm": True,
                                         "route_scale": 2.5}),
    "joyai.decode": dict(rows=32, top_k=8, held=16, routed=256, hid=2048,
                         ffn=768, routing={"score_func": "sigmoid",
                                           "route_norm": True,
                                           "route_scale": 2.5}),
}
SCOPES = ("router", "sort", "experts", "combine")


def part_of(event_name: str, op_name: str) -> str:
    """``scope`` or ``scope.kind`` of one device operation."""
    scope = next((s for s in SCOPES
                  if hlo_names.scope_pattern(s).search(op_name)), "other")
    if xplane.is_mosaic(event_name) or "ragged-dot" in event_name:
        kind = "products"
    elif xplane.opcode(event_name) == "sort" or op_name.endswith("/sort"):
        kind = "sort"
    elif op_name.endswith(("gather", "dynamic_slice")):
        kind = "gather"
    else:
        kind = "rest"
    return scope if scope in ("router", "other") else f"{scope}.{kind}"


def capture(fn, args, calls: int):
    """``(us a call, {part: us a call}, [(op_name, instruction, us)])``."""
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            for _ in range(calls):
                out = fn(*args)
            jax.block_until_ready(out)
        path, = glob.glob(os.path.join(tmp, "plugins/profile/*/*.xplane.pb"))
        trace, names = xplane.load(path), hlo_names.load(path)
    parts, ops = {}, {}
    for ev, t, op_name in named.named_ops(trace, names):
        us = t / calls / 1e3
        part = part_of(ev.name, op_name)
        parts[part] = parts.get(part, 0.0) + us
        key = (op_name, xplane.instruction(ev.name))
        ops[key] = ops.get(key, 0.0) + us
    total = sum(parts.values())
    big = sorted(((o, i, round(us, 1)) for (o, i), us in ops.items()
                  if us >= 0.01 * total), key=lambda r: -r[2])
    return total, {k: round(v, 1) for k, v in sorted(parts.items())}, big


def layer(shape, seed: int):
    """One MoE layer's weights at ``shape``: the router ``[hid, routed]`` and
    the held experts' SwiGLU stacks, bfloat16."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    hid, ffn, E = shape["hid"], shape["ffn"], shape["held"]
    stack = lambda k, a, b: (jax.random.normal(k, (E, a, b), jnp.float32)
                             * a ** -0.5).astype(jnp.bfloat16)
    # logits of unit variance: a sigmoid router whose scores saturate ties
    # at 1.0, and top-k then takes the lowest ids — the held ones
    w = {"router": jax.random.normal(keys[0], (hid, shape["routed"]),
                                     jnp.float32) * hid ** -0.5,
         "w_gate": stack(keys[1], hid, ffn), "w_up": stack(keys[2], hid, ffn),
         "w_down": stack(keys[3], ffn, hid)}
    if shape["routing"].get("score_func") == "sigmoid":
        w["expert_bias"] = jnp.zeros((shape["routed"],), jnp.float32)
    x = jax.random.normal(keys[4], (shape["rows"], hid), jnp.bfloat16)
    return w, x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--calls", type=int, default=12)
    ap.add_argument("--seed", type=int, default=53)
    ap.add_argument("--out", default="chiprun_out/moe_held_table.jsonl")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"moe_held_table: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 2
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "a") as out:
        for name in args.shapes.split(","):
            shape = SHAPES[name]
            w, x = layer(shape, args.seed)
            T, K, E = shape["rows"], shape["top_k"], shape["held"]
            routing = {"num_experts": shape["routed"], "top_k": K,
                       "held": (0, E), **shape["routing"]}
            bound = rm.held_rows_bound(T * K, E, shape["routed"],
                                       rm.moe_grouped_kernel(w["w_up"],
                                                             x.dtype))
            rng = np.random.default_rng(args.seed)
            every = (jnp.asarray(rng.uniform(0.05, 0.2, (T, K)), jnp.float32),
                     jnp.asarray(rng.integers(0, E, (T, K)), jnp.int32))

            def run(compact):
                turns = jnp.zeros((), jnp.int32) if compact else None
                return jax.jit(lambda x, w, routed: rm._moe_ffn(
                    x, w, K, jnp.bfloat16, routing=routing, routed=routed,
                    turns=turns))

            want = {}
            for routed_name, routed in (("router", None), ("overflow", every)):
                for version in ("every_choice", "compact"):
                    fn = run(version == "compact")
                    a = (x, w, routed)
                    got = jax.block_until_ready(fn(*a))
                    turns = 0
                    if version == "compact":
                        got, turns = got[0], int(got[1])
                    got = np.asarray(got, np.float32)
                    ref = want.setdefault(routed_name, got)
                    total, parts, big = capture(fn, a, args.calls)
                    line = {"shape": name, "routing": routed_name,
                            "version": version, "rows": T, "choices": T * K,
                            "bound": bound, "overflow_turns": turns,
                            "device": dev.device_kind,
                            "us_call": round(total, 1), "parts": parts,
                            "max_abs_diff": float(np.abs(got - ref).max()),
                            "abs_max": float(np.abs(ref).max())}
                    print(json.dumps(line), flush=True)
                    out.write(json.dumps({**line, "ops": big}) + "\n")
                    out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
