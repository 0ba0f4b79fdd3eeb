"""``dsa_select`` — the exact k-th largest index score of a query row — timed
by itself on the chip at GLM-5's two shapes.

    chiprun --timeout 1500 -- python3 scripts/dsa_select_table.py [--other path/to/sparse_mla.py ...] [--blocks 8x8,32x2]

It is the table in PERF.md (PR 60); run it again when the kernel, the
compiler or the chip changes. Cell 14's two calls of ``sparse_mla.select``:

- a paged pass's, ``[8, 68, 256, 512]``: 8 slots of 256 query tokens of ONE
  sequence, 68 tiles of 512 keys (272 pages of 128), the 2,048 best kept,
  with the pass's LAST token at 4k / 9k / 18k / 32k;
- the decode step's, ``[1, 17, 16, 2,048]``: 16 rows as the query rows of
  one slot, 17 tiles of 2,048 keys, the rows' contexts spread evenly over
  the cell's 8k to 32k.

Scores are drawn twice: ``normal`` (unit normal where a query may look,
``-inf`` elsewhere) and ``indexer`` — what ``sparse_mla.index_scores`` makes
of a drawn GLM-5 indexer: ``ragged_model._index_project`` at the published
widths (32 heads of 128, 64 rotated, from a query latent of 2,048 and a
hidden row of 6,144, the key through its LayerNorm) over normal hidden rows
and query latents, its three matrices normal at a fan-in's scale.

A line gives ``us``, the microseconds of the Mosaic call ``dsa_select`` from
a profiler capture; ``walks_block``, the walks over a block's tiles (mean
and largest over the call's blocks of query rows: the kernel's own count
where it hands one back, ``1 + 32 + 2`` for a copy of the file that sweeps
every bit); ``ns_register_walk``, ``us`` over the float32 registers (8 x
128) those walks read — the tiles at or under each slot's context, every
row of them, times the walks; ``hbm_read_share``, the time of ONE read of
the call's whole ``[N, C, R, T]`` at the chip's HBM rate as a percentage of
``us``; and ``same``: whether ``(thr, pcut)`` equal the first version's bit
for bit. ``--other`` names further copies of ``ops/pallas/sparse_mla.py`` to
time beside this tree's, ``--blocks`` further ``rows x tiles an iteration``
for each copy that has a ``_select_block`` beside what it picks. Lines also go to
``chiprun_out/dsa_select_table.jsonl``. No chip, no number: it exits 2.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from deepspeed_tpu.inference.v2 import ragged_model  # noqa: E402
from deepspeed_tpu.models.glm_dsa import GlmDsaConfig  # noqa: E402
from deepspeed_tpu.ops.pallas import sparse_mla  # noqa: E402
from dsa_chunk_table import GLM5, device_us  # noqa: E402  (beside this file)
from gdn_scan_table import load  # noqa: E402

CONTEXTS = (4096, 9216, 18432, 32768)
ROWS = 16                 # the cell's decode rows
BF16 = jnp.bfloat16


def positions(case):
    """``(q0 [N], ctx [N], rows a slot)`` of a case: a context (the paged
    pass whose last token stands there) or ``"step"``."""
    if case == "step":
        ctx = np.linspace(8192, 32768, ROWS).astype(np.int64)
        return ctx - 1, ctx, 1
    N, Cs = GLM5["slots"], GLM5["rows"]
    q0 = np.asarray([case - (N - i) * Cs for i in range(N)])
    assert q0.min() >= 0, "the context is shorter than the pass"
    return q0, q0 + Cs, Cs


def indexer(cfg: GlmDsaConfig, key):
    """A drawn indexer's weights and the spec ``_index_project`` reads."""
    Hi, Di = cfg.index_n_heads, cfg.index_head_dim
    draw = lambda i, fan, *shape: (jax.random.normal(
        jax.random.fold_in(key, i), shape, jnp.float32) * fan ** -0.5
    ).astype(BF16)
    wi = {"wq": draw(0, cfg.q_lora_rank, cfg.q_lora_rank, Hi * Di),
          "wk": draw(1, cfg.hidden_size, cfg.hidden_size, Di),
          "ww": draw(2, cfg.hidden_size, cfg.hidden_size, Hi),
          "k_norm": jnp.ones((Di,), BF16), "k_bias": jnp.zeros((Di,), BF16)}
    spec = types.SimpleNamespace(
        dtype=BF16, rope_theta=cfg.rope_theta,
        mla={"index": {"heads": Hi, "head_dim": Di,
                       "rope_dim": cfg.qk_rope_head_dim,
                       "eps": cfg.index_norm_eps}})
    return spec, wi


def scores_of(case, filling: str, seed: int = 60):
    """``(tiled scores [N, C, R, T] as the caller hands them to select, k
    [N, R], ctx [N])`` of a case."""
    q0, ctx, R = positions(case)
    N, MB, bs, topk = len(q0), GLM5["pages"], GLM5["block"], GLM5["topk"]
    key = jax.random.PRNGKey(seed)
    q0, ctx = jnp.asarray(q0, jnp.int32), jnp.asarray(ctx, jnp.int32)
    T = sparse_mla.tile_pages(bs, MB, sparse_mla.DECODE_TILE if R == 1
                              else sparse_mla.CHUNK_TILE) * bs
    C = -(-MB * bs // T)
    if filling == "normal":
        @jax.jit
        def make(q0, ctx):
            pos = jnp.arange(C * T)[None, None]
            q_pos = q0[:, None, None] + jnp.arange(R)[None, :, None]
            s = jax.random.normal(key, (N, R, C * T), jnp.float32)
            s = jnp.where((pos <= q_pos) & (pos < ctx[:, None, None]), s,
                          -jnp.inf)
            return s.reshape(N, R, C, T).transpose(0, 2, 1, 3)
    else:
        cfg = GlmDsaConfig.glm_5(dtype=BF16)
        spec, wi = indexer(cfg, key)
        S = MB * bs
        rng = np.random.default_rng(seed)
        table = jnp.asarray(rng.permutation(MB) + 1, jnp.int32)

        @jax.jit
        def make(q0, ctx):
            h = jax.random.normal(jax.random.fold_in(key, 3),
                                  (S, cfg.hidden_size), BF16)
            _, _, keys = ragged_model._index_project(
                spec, wi, h, jnp.zeros((S, cfg.q_lora_rank), BF16),
                jnp.arange(S, dtype=jnp.int32))
            rows = (q0[:, None] + jnp.arange(R, dtype=jnp.int32)).reshape(-1)
            cq = jax.random.normal(jax.random.fold_in(key, 4),
                                   (N * R, cfg.q_lora_rank), BF16)
            q, w, _ = ragged_model._index_project(spec, wi, h[rows], cq, rows)
            pages = jnp.zeros((MB + 1, bs, keys.shape[-1]), BF16)
            pages = pages.at[table].set(keys.reshape(MB, bs, -1))
            return sparse_mla.index_scores(
                q.reshape((N, R) + q.shape[1:]), w.reshape(N, R, -1), pages,
                jnp.broadcast_to(table[None], (N, MB)), q0, ctx)

    sc = make(q0, ctx)
    seen = jnp.minimum(ctx[:, None], q0[:, None] + 1 + jnp.arange(R)[None])
    k = jnp.clip(jnp.minimum(seen, topk), 1).astype(jnp.int32)
    if R == 1:           # ragged_mla.select_decode: the rows as ONE slot's
        return sc.transpose(2, 1, 0, 3), k.T, jnp.max(ctx, keepdims=True)
    return sc, k, ctx


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cases", default=",".join(map(str, CONTEXTS)) + ",step")
    ap.add_argument("--fillings", default="normal,indexer")
    ap.add_argument("--other", action="append", default=[],
                    help="another copy of ops/pallas/sparse_mla.py")
    ap.add_argument("--blocks", default="",
                    help="rows x tiles an iteration to time beside the picked")
    ap.add_argument("--calls", type=int, default=5)
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("dsa_select_table: no TPU here, and a time comes only from one",
              file=sys.stderr)
        return 2
    with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
            __file__))), "chipbench", "peaks.json")) as f:
        hbm = json.load(f)[jax.devices()[0].device_kind]["hbm_bytes_per_s"]
    modules = [("tree", sparse_mla)] + [
        (path, load(path, f"sparse_mla_other{i}"))
        for i, path in enumerate(args.other)]
    versions = [(name, module, None) for name, module in modules]
    versions += [(f"{name} {b}", module, tuple(map(int, b.split("x"))))
                 for name, module in modules
                 for b in filter(None, args.blocks.split(","))
                 if hasattr(module, "_select_block")]
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/dsa_select_table.jsonl", "a") as out:
        for filling in args.fillings.split(","):
            for case in args.cases.split(","):
                case = case if case == "step" else int(case)
                sc, k, ctx = scores_of(case, filling)
                N, C, R, T = sc.shape
                tiles = np.clip(-(-np.asarray(ctx) // T), 0, C)
                first = None
                for version, module, block in versions:
                    held = getattr(module, "_select_block", None)
                    if block is not None:
                        module._select_block = lambda *_, b=block: b
                    try:        # (a fresh program: another block's trace)
                        counted = getattr(module, "select_counted", None)
                        fn = jax.jit(lambda *a, f=counted or module.select:
                                     f(*a))
                        got = jax.block_until_ready(fn(sc, k, ctx))
                        us = device_us(fn, (sc, k, ctx), args.calls, True)
                    except Exception as e:   # a block VMEM cannot hold
                        print(json.dumps({"case": case, "version": version,
                                          "error": repr(e)[:300]}),
                              flush=True)
                        continue
                    finally:
                        if block is not None:
                            module._select_block = held
                    walks = np.asarray(got[2]) if counted else np.full(
                        (N, 1), 35)
                    first = first or got
                    read = float(np.sum(tiles[:, None] * walks.mean(
                        axis=1, keepdims=True))) * R * T / 1024
                    line = {
                        "case": case, "filling": filling, "version": version,
                        "shape": [N, C, R, T],
                        "block": list(block or (held(R, C, T) if held
                                                else (min(R, 16), 1))),
                        "us": round(us, 2),
                        "walks_block": [round(float(walks.mean()), 2),
                                        int(walks.max())],
                        "ns_register_walk": round(us * 1e3 / read, 4),
                        "hbm_read_share": round(
                            100 * N * C * R * T * 4 / hbm * 1e6 / us, 2),
                        "same": all(bool(jnp.array_equal(a, b, equal_nan=True))
                                    for a, b in zip(got[:2], first[:2])),
                        "device": jax.devices()[0].device_kind}
                    print(json.dumps(line), flush=True)
                    out.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
