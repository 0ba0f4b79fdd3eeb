"""A prompt chunk's attention over its selection timed by itself on the chip at
GLM-5's widths: the absorbed kernel under the mask against the expanded one.

    chiprun --timeout 1500 -- python3 scripts/dsa_chunk_table.py [--groups 2,8] [--other path/to/sparse_mla.py ...]

It is the table in PERF.md (PR 58); run it again when either kernel, the
compiler or the chip changes. A pass of cell 14: 8 slots of 256 query tokens,
64 heads (q/k 192 + 64, v 256) over latent rows of 640 (512 + 64 + padding),
pages of 128, 272-page tables, the 2,048 best kept. Index scores are drawn
(unit normal where a query may look, ``-inf`` elsewhere) and go through
``sparse_mla.select`` as the pass's do, so the mask is a real selection's.
Contexts of 4k / 9k / 18k / 32k are where the pass's LAST token stands; ``one``
sequence a pass (8 consecutive slots over one block table: what the paged
pass hands ``attend_expanded``) and ``two`` (4 slots each, a table each: what
stays with ``attend_chunk``; the expanded kernel is timed there as two calls
of 4 slots, what engaging it a sequence would cost). A line gives the
microseconds of

- ``absorbed_us``: the Mosaic call ``dsa_attend_chunk``;
- ``absorb_us``: ``W_UK`` into the 2,048 tokens' queries and ``W_UV`` onto
  their outputs (XLA; the absorbed path's alone);
- ``expanded_us``: the Mosaic call ``dsa_attend_expanded``, the expansion of
  each tile of latent rows inside it;
- ``expansion_us``: the pages below the context through ``w_kv`` by XLA alone
  (what an expansion into HBM would cost at the least; no part of either
  path);
- ``weights_us``: ``w_kv`` built from ``w_uk`` and ``w_uv`` and the queries
  laid out a head (XLA; the expanded path's alone);

each from a profiler capture, the share of the MXU's peak each kernel reaches
on the operations IT issues for the keys its rows see (absorbed ``2 (W +
R)`` a (query, key, head); expanded ``2 (k + v)`` and ``2 W (k + v)`` a (key,
head) of expansion), and how far the expanded output is from the absorbed
one (root mean square, relative). ``--groups`` also times the expanded kernel
at other head groups a grid step than it picks, ``--other`` names further
copies of ``ops/pallas/sparse_mla.py`` to time beside this tree's. Lines also
go to ``chiprun_out/dsa_chunk_table.jsonl``. No chip, no number: it exits 2.
"""

from __future__ import annotations

import argparse
import functools
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
from deepspeed_tpu.inference.v2 import ragged_mla  # noqa: E402
from deepspeed_tpu.ops.pallas import sparse_mla  # noqa: E402
from gdn_scan_table import load, rel  # noqa: E402  (beside this file)

GLM5 = dict(slots=8, rows=256, heads=64, nope=192, rope=64, v=256, rank=512,
            width=640, block=128, pages=272, topk=2048)
CONTEXTS = (4096, 9216, 18432, 32768)
BF16 = jnp.bfloat16


def device_us(fn, args, calls: int, mosaic: bool) -> float:
    """Microseconds a run of ``fn`` on the device, from a capture: its Mosaic
    calls alone, or everything but them."""
    jax.block_until_ready(fn(*args))          # compiled before the capture
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            for _ in range(calls):
                out = fn(*args)
            jax.block_until_ready(out)
        path, = glob.glob(os.path.join(tmp, "plugins/profile/*/*.xplane.pb"))
        dev = next(iter(xplane.load(path).devices.values()))
    return sum(t for ev, t in dev.self_times()
               if xplane.is_mosaic(ev.name) == mosaic) / calls / 1e3


def arguments(d: dict, context: int, sequences: int, seed: int = 58):
    """One pass's arguments: ``sequences`` sequences of ``slots //
    sequences`` consecutive slots each, the last token of each at ``context -
    1``; every sequence's pages its own."""
    N, Cs, H, MB, bs = (d[k] for k in ("slots", "rows", "heads", "pages",
                                       "block"))
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    draw = lambda i, *shape: jax.random.normal(jax.random.fold_in(key, i),
                                               shape, BF16)
    NB = sequences * MB + 1
    lat = draw(0, NB, bs, d["width"])
    lat = lat * (jnp.arange(d["width"]) < d["rank"] + d["rope"])
    per = N // sequences
    tables = (rng.permutation(NB - 1) + 1).reshape(sequences, MB)
    q0 = np.asarray([context - (per - i % per) * Cs for i in range(N)])
    assert q0.min() >= 0, "the context is shorter than a sequence's slots"
    T = sparse_mla.tile_pages(bs, MB, sparse_mla.CHUNK_TILE) * bs
    C = -(-MB * bs // T)

    @jax.jit
    def scores(q0):
        pos = jnp.arange(C * T)[None, None]
        q_pos = q0[:, None, None] + jnp.arange(Cs)[None, :, None]
        s = jax.random.normal(jax.random.fold_in(key, 9), (N, Cs, C * T),
                              jnp.float32)
        s = jnp.where(pos <= q_pos, s, -jnp.inf)
        return s.reshape(N, Cs, C, T).transpose(0, 2, 1, 3)

    q0 = jnp.asarray(q0, jnp.int32)
    ctx = q0 + Cs
    sc = scores(q0)
    seen = jnp.minimum(ctx[:, None], q0[:, None] + 1 + jnp.arange(Cs)[None])
    thr, pcut = sparse_mla.select(sc, jnp.minimum(seen, d["topk"]), ctx)
    return dict(
        q_nope=draw(1, N * Cs, H, d["nope"]), q_rope=draw(2, N * Cs, H,
                                                           d["rope"]),
        w={"w_uk": draw(3, H, d["rank"], d["nope"]) * d["rank"] ** -0.5,
           "w_uv": draw(4, H, d["rank"], d["v"]) * d["rank"] ** -0.5},
        lat=lat, tables=jnp.asarray(np.repeat(tables, per, axis=0),
                                    jnp.int32),
        q0=q0, ctx=ctx, scores=sc, thr=thr, pcut=pcut)


def visible_keys(q0, ctx) -> int:
    """Keys the pass's query tokens see, summed over tokens."""
    return int(sum(np.sum(np.arange(a, b) + 1)
                   for a, b in zip(np.asarray(q0), np.asarray(ctx))))


def programs(d: dict, sequences: int, module=sparse_mla):
    """The jitted pieces of both paths (module docstring), each over the
    pass's arrays ``a`` (:func:`arguments`) as its first argument; the
    kernels are ``module``'s."""
    N, Cs, H, W, R = (d[k] for k in ("slots", "rows", "heads", "width",
                                     "rank"))
    scale = (d["nope"] + d["rope"]) ** -0.5
    k_dim = d["nope"] + d["rope"]
    per = N // sequences
    absorb_q = lambda a: ragged_mla.mla_absorb_q(
        None, a["w"], a["q_nope"], a["q_rope"], W).reshape(N, Cs * H, W)
    absorb_o = lambda a, o: ragged_mla.mla_absorb_o(
        a["w"], o.reshape(N * Cs, H, R))
    absorbed = lambda a, q: module.attend_chunk(
        q, a["lat"], a["tables"], a["q0"], a["ctx"], a["scores"], a["thr"],
        a["pcut"], heads=H, v_dim=R, softmax_scale=scale)
    weights = lambda a: (
        ragged_mla.expansion_weights(a["w"], R, d["rope"], W),
        ragged_mla.expanded_queries(a["q_nope"], a["q_rope"], N))

    def expanded(a, w_kv, q):
        outs = [module.attend_expanded(
            q[s:s + per], w_kv, a["lat"], a["tables"][s], a["ctx"][s:s + per],
            a["scores"][s:s + per], a["thr"][s:s + per],
            a["pcut"][s:s + per], k_dim=k_dim, softmax_scale=scale)
            for s in range(0, N, per)]
        return jnp.concatenate(outs).reshape(N * Cs, -1)

    def expansion(a, w_kv, pages):
        rows = a["lat"][a["tables"][0, :pages]].reshape(-1, W)
        return jnp.einsum("tw,hwd->htd", rows, w_kv,
                          preferred_element_type=jnp.float32).astype(BF16)

    jitted = {k: jax.jit(v) for k, v in dict(
        absorb_q=absorb_q, absorb_o=absorb_o, absorbed=absorbed,
        weights=weights, expanded=expanded).items()}
    return dict(jitted, expansion=jax.jit(expansion, static_argnames="pages"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--contexts", default=",".join(map(str, CONTEXTS)))
    ap.add_argument("--sequences", default="1,2")
    ap.add_argument("--other", action="append", default=[],
                    help="another copy of ops/pallas/sparse_mla.py")
    ap.add_argument("--groups", default="",
                    help="head groups a grid step to time beside the picked")
    ap.add_argument("--calls", type=int, default=5)
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("dsa_chunk_table: no TPU here, and a time comes only from one",
              file=sys.stderr)
        return 2
    with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
            __file__))), "chipbench", "peaks.json")) as f:
        peak = json.load(f)[jax.devices()[0].device_kind]["bf16_flops_per_s"]
    d = GLM5
    H, W, R = d["heads"], d["width"], d["rank"]
    kv = d["nope"] + d["rope"] + d["v"]
    versions = {"tree": sparse_mla}
    for i, path in enumerate(args.other):
        versions[path] = load(path, f"sparse_mla_other{i}")
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/dsa_chunk_table.jsonl", "a") as out:
        for context in map(int, args.contexts.split(",")):
            for sequences in map(int, args.sequences.split(",")):
                a = arguments(d, context, sequences)
                keys = visible_keys(a["q0"], a["ctx"])
                tiles = -(-context // 512) * 512 * sequences
                pages = -(-context // d["block"])
                for version, module in versions.items():
                    p = programs(d, sequences, module)
                    us = lambda name, *x, mosaic=False, **kw: device_us(
                        functools.partial(p[name], **kw), (a,) + x,
                        args.calls, mosaic)
                    q_abs = p["absorb_q"](a)
                    o_lat = p["absorbed"](a, q_abs)
                    w_kv, q_exp = p["weights"](a)
                    line = {
                        "context": context, "sequences": sequences,
                        "version": version,
                        "absorbed_us": us("absorbed", q_abs, mosaic=True),
                        "absorb_us": us("absorb_q") + us("absorb_o", o_lat),
                        "expanded_us": us("expanded", w_kv, q_exp,
                                          mosaic=True),
                        "expansion_us": us("expansion", w_kv, pages=pages),
                        "weights_us": us("weights"),
                        "from_absorbed": rel(
                            p["expanded"](a, w_kv, q_exp).astype(jnp.float32),
                            p["absorb_o"](a, o_lat).astype(jnp.float32)),
                        "device": jax.devices()[0].device_kind}
                    line["absorbed_mxu_share"] = 100 * (
                        2 * (W + R) * H * keys / peak * 1e6
                        / line["absorbed_us"])
                    line["expanded_mxu_share"] = 100 * (
                        (2 * kv * H * keys + 2 * W * kv * H * tiles) / peak
                        * 1e6 / line["expanded_us"])
                    for g in filter(None, args.groups.split(",")):
                        held = module._expanded_group
                        module._expanded_group = lambda *_, g=int(g): g
                        try:      # (a fresh program: another group's trace)
                            line[f"expanded_us_group{g}"] = device_us(
                                programs(d, sequences, module)["expanded"],
                                (a, w_kv, q_exp), args.calls, True)
                        except Exception as e:   # a group VMEM cannot hold
                            line[f"expanded_us_group{g}"] = repr(e)[:200]
                        finally:
                            module._expanded_group = held
                    line = {k: round(v, 2) if isinstance(v, float) and k != (
                        "from_absorbed") else v for k, v in line.items()}
                    print(json.dumps(line), flush=True)
                    out.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
