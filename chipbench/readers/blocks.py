"""Per-layer metrics of BLOCK DECODE — generation by diffusion over blocks
(``deepspeed_tpu/inference/v2/blocks/pipeline.py``) — read from a ``--trace
2`` capture: what the program's always-on ``serve/block/*`` counters gained
over the captured interval, and the block step's attention against its
roofline as ``readers/paged.py`` reads the paged kernels' (its
``calls_an_execution`` and ``records``).

- ``tokens_per_row_pass``: tokens committed to requests over row-passes (a
  live row in a pass): what a pass yields a row — 4/3 where a block of 4
  takes 2 denoise passes and a commit, less what first and last blocks
  lose;
- ``commit_share``: of the row-passes, the share that are commit passes, in
  percent;
- ``attend_roofline_share``: ``paged_chunk`` under ``block_step`` in the
  block-step programs, against the captured ``serve/block/step`` spans'
  ``rows`` and ``ctx_tokens`` through ``chipbench/reduce/block_work.py``,
  summed over the layers: bytes bind it.

The roofline reader returns nothing where the view has no capture or the
program no such span or call (a program that has no block step): the metric
is then absent. The two counters' readers give 0.0 there."""

from chipbench.readers.paged import _floor_s, calls_an_execution, records
from chipbench.reduce import block_work, kv_work

PROGRAMS = ("jit_serve_block_step",)
SCOPE = r"block_step/(?:[^/]+/)*attn_full/paged_chunk\w*"


def _over_row_passes(view, name):
    """What the counter ``serve/block/<name>`` gained over what
    ``serve/block/row_passes`` gained: over the captured interval where the
    view holds a capture in which a pass ran (steady traffic), else over the
    process (its check, warm-up burst and ramp too: the same rule at work).
    0.0, not nothing, where the program has counted no row-pass at all
    (``readers/totals.py``'s way: a counter may not be absent)."""
    from deepspeed_tpu.monitor.trace import tracer
    capture = view.get("capture")
    counters = getattr(capture, "counters", None) or {}
    if not counters.get("serve/block/row_passes"):
        counters = dict(tracer.totals)
    row_passes = counters.get("serve/block/row_passes")
    if not row_passes:
        return 0.0
    return counters.get(f"serve/block/{name}", 0.0) / row_passes


def tokens_per_row_pass(view):
    return _over_row_passes(view, "tokens_committed")


def commit_share(view):
    return 100.0 * _over_row_passes(view, "commit_row_passes")


def attend_reading(view):
    """The block step's attention kernel's reading, or None: ``share`` in
    percent, the kernel's and the floor's microseconds a pass."""
    config = view.get("config", {})
    if view.get("capture") is None or not view.get("op_names") \
            or "block_length" not in config:
        return None
    try:
        w = kv_work.widths(config)
    except (KeyError, AttributeError, ImportError):
        return None
    block = int(config["block_length"])
    floors = [w["full_layers"] * _floor_s(block_work.block_call(
        w, block, a["rows"], a["ctx_tokens"]), view["peaks"])
        for a in records(view, "serve/block/step", "rows", "ctx_tokens")
        if a["rows"]]
    ns, runs = calls_an_execution(view["trace"], view["op_names"], SCOPE,
                                  PROGRAMS)
    if not ns or not floors:
        return None
    floor_s, seconds = sum(floors) / len(floors), ns / runs * 1e-9
    return {"share": 100.0 * floor_s / seconds, "executions": runs,
            "records": len(floors), "kernel_us": seconds * 1e6,
            "floor_us": floor_s * 1e6}


def attend_roofline_share(view):
    reading = attend_reading(view)
    return None if reading is None else reading["share"]
