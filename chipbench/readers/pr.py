"""The two power-retention kernels' shares of their rooflines, read from a
``--trace 2`` capture as ``readers/paged.py`` reads the paged kernels' (its
``calls_an_execution`` and ``records``): a
kernel's device time from the trace (its Mosaic calls under its scope, summed
over a whole EXECUTION of the programs that run it for traffic — a layer loop
calls it once a layer), what it had to do from what the program says its
rows held (the arguments of the ``serve/decode/step`` and
``serve/prefill/pass`` spans inside the capture), through
``chipbench/reduce/pr_work.py``.

- ``step_roofline_share``: ``pr_decode_step`` under ``pr/step`` in the
  decode-step programs; the captured steps' ``live`` rows, each layer's call
  held to ``rows x bytes a row`` over the chip's HBM rate.
- ``scan_roofline_share``: ``pr_chunk_scan`` under ``pr/scan`` in both pass
  programs; a captured pass's prompt tokens and live chunk slots (``ntok``),
  each layer's call held to the larger of its operations over the bfloat16
  peak and its bytes over the HBM rate.

Only live rows count, and each product once, so a reading is a lower bound.
A reader returns nothing where the view has no capture, the configuration
none of the kernels' widths, the trace no such call or the capture no record
with the arguments (a program that has no such layer): the metric is then
absent."""

from chipbench.readers.paged import calls_an_execution, records
from chipbench.reduce import mla_work, pr_work

STEP_PROGRAMS = ("jit_serve_decode_step",)
PASS_PROGRAMS = ("jit_serve_prefill_packed", "jit_serve_paged_pass")
STEP_SCOPE = r"pr/step/pr_decode_step"
SCAN_SCOPE = r"pr/scan/pr_chunk_scan"


def _widths(view):
    if view.get("capture") is None or not view.get("op_names"):
        return None
    try:
        return pr_work.widths(view["config"])
    except (KeyError, TypeError):
        return None


def _reading(view, scope, programs, works, layers):
    """``works``: ``(operations, bytes)`` of one layer's call, a captured
    record; the least seconds an execution's ``layers`` calls could take
    against the seconds they took."""
    ns, runs = calls_an_execution(view["trace"], view["op_names"], scope,
                                  programs)
    if not ns or not works:
        return None
    floors = [mla_work.roofline(f, b, 1.0, view["peaks"]) for f, b in works]
    floor_s = layers * sum(max(r["compute_s"], r["memory_s"])
                           for r in floors) / len(floors)
    seconds = ns / runs * 1e-9
    if not floor_s:
        return None
    return {"share": 100.0 * floor_s / seconds, "executions": runs,
            "records": len(works), "kernel_us": seconds * 1e6,
            "floor_us": floor_s * 1e6,
            "bound": max(floors, key=lambda r: max(
                r["compute_s"], r["memory_s"]))["bound"]}


def step_reading(view):
    """The decode kernel's reading, or None: ``share`` in percent, the
    kernel's and the floor's microseconds a step, steps counted."""
    w = _widths(view)
    if w is None:
        return None
    works = [pr_work.decode_call(a["live"], w["heads"], w["kv_heads"], w["d"])
             for a in records(view, "serve/decode/step", "live") if a["live"]]
    return _reading(view, STEP_SCOPE, STEP_PROGRAMS, works, w["layers"])


def scan_reading(view):
    """The chunked scan's reading over the captured passes, or None."""
    w = _widths(view)
    if w is None:
        return None
    works = [pr_work.scan_call(sum(a["ntok"]), len(a["ntok"]), w["heads"],
                               w["kv_heads"], w["d"], w["chunk"])
             for a in records(view, "serve/prefill/pass", "ntok")
             if sum(a["ntok"])]
    return _reading(view, SCAN_SCOPE, PASS_PROGRAMS, works, w["layers"])


def _share(reading):
    return None if reading is None else reading["share"]


def step_roofline_share(view):
    return _share(step_reading(view))


def scan_roofline_share(view):
    return _share(scan_reading(view))
