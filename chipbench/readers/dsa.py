"""The selection's kernels' shares of their rooflines, read from a ``--trace
2`` capture as ``readers/paged.py`` reads the paged kernels' (its
``calls_an_execution`` and ``records``): a kernel's device time from the
trace (its Mosaic calls under its scope, summed over a whole EXECUTION of
the programs that run it for traffic — a layer loop calls it once a layer),
what it had to do from what the program says its rows held (the arguments
of the ``serve/decode/step`` and ``serve/prefill/pass`` spans inside the
capture), through ``chipbench/reduce/dsa_work.py``.

- ``index_decode_roofline_share`` / ``attend_decode_roofline_share``:
  ``dsa_index_decode`` under ``index/score``, and the compaction, XLA's
  gather of the chosen rows (``index/gather``) AND ``dsa_attend_decode``
  under ``mla/decode``, in the decode-step programs, against the captured
  steps' ``live`` rows and the sum of their contexts ``ctx``. Attention's count
  needs ``min(ctx, topk)`` a row and the span gives the sum: a row's context
  is taken as the step's mean (in the cell that reports it every context is
  over ``topk``, so the count is ``live x topk`` whatever the spread).
- ``index_chunk_roofline_share`` / ``attend_chunk_roofline_share``:
  ``dsa_index_chunk`` and ``dsa_attend_chunk`` in the paged-pass program,
  against the passes of ``kind == "paged"`` (``ntok``, ``cached``). The
  paged pass's decode rows run the decode kernels too: those calls are
  under other names and are not counted here.

Only live rows count, each product once, attention at the positions the
selection keeps: a reading is a lower bound. A reader returns nothing where
the view has no capture, the configuration none of the kernels' widths, the
trace no such call or the capture no record with the arguments (a program
that has no such kernel): the metric is then absent."""

from chipbench.readers.paged import records
from chipbench.reduce import dsa_work, hlo_names, mla_work, named, xplane

STEP_PROGRAMS = ("jit_serve_decode_step",)
PASS_PROGRAMS = ("jit_serve_paged_pass",)
#: what runs a kernel's work, by scope. A decode row's attention over its
#: chosen rows is TWO pieces of the program: XLA's compaction and gather of
#: the rows out of the latent pool (``index/gather``: the read of the pool
#: that the count below is about) and ``dsa_attend_decode`` over what was
#: gathered, which the compiler may hand it in on-chip memory: the kernel
#: by itself read 122% of the HBM rate (my chip run, PR 57)
SCOPES = {"index_decode": r"index/score/dsa_index_decode",
          "attend_decode": r"index/gather|mla/decode/dsa_attend_decode",
          "index_chunk": r"index/score/dsa_index_chunk",
          "attend_chunk": r"mla/prefill/dsa_attend_chunk"}


def ops_an_execution(trace, op_names, scope, programs):
    """``(nanoseconds, executions)``: the self time of EVERY operation under
    ``scope`` (``readers/paged.py::calls_an_execution`` counts the Mosaic
    calls alone) inside a whole execution of one of ``programs``, and how
    many such executions there are; a chip's first and last execution are
    left out where it has three or more."""
    pattern = hlo_names.scope_pattern(scope)
    total, runs = 0.0, 0
    for dev in trace.devices.values():
        whole = dev.modules[1:-1] if len(dev.modules) >= 3 else dev.modules
        mine = [m for m in whole
                if named._program(m.name).startswith(programs)]
        runs += len(mine)
        k = 0
        for ev, t in dev.self_times():
            while k < len(mine) and mine[k].end_ns < ev.start_ns:
                k += 1
            if k == len(mine):
                break
            if mine[k].start_ns <= ev.start_ns and pattern.search(
                    op_names.get(mine[k].name, {}).get(
                        xplane.instruction(ev.name).lstrip("%"), "")):
                total += t
    return total, runs


def _reading(view, kernel, programs, works, layers):
    """``works``: ``(operations, bytes)`` of one layer's call, a captured
    record; the least seconds an execution's ``layers`` calls could take
    against the seconds the operations under the kernel's scopes took."""
    ns, runs = ops_an_execution(view["trace"], view["op_names"],
                                SCOPES[kernel], programs)
    if not ns or not works:
        return None
    floors = [mla_work.roofline(f, b, 1.0, view["peaks"]) for f, b in works]
    floor_s = layers * sum(max(r["compute_s"], r["memory_s"])
                           for r in floors) / len(floors)
    if not floor_s:
        return None
    seconds = ns / runs * 1e-9
    return {"share": 100.0 * floor_s / seconds, "executions": runs,
            "records": len(works), "kernel_us": seconds * 1e6,
            "floor_us": floor_s * 1e6}


def _widths(view):
    if view.get("capture") is None or not view.get("op_names"):
        return None
    try:
        return dsa_work.widths(view["config"])
    except (KeyError, TypeError):
        return None


def _steps(view):
    return [a for a in records(view, "serve/decode/step", "live", "ctx")
            if a["live"]]


def _passes(view):
    return [a for a in records(view, "serve/prefill/pass", "kind", "ntok",
                               "cached")
            if a["kind"] == "paged" and sum(a["ntok"])]


def reading(view, kernel):
    """The reading of one of the four kernels (``SCOPES``), or None."""
    w = _widths(view)
    if w is None:
        return None
    if kernel == "index_decode":
        works = [dsa_work.index_decode_call(w, a["live"], a["ctx"])
                 for a in _steps(view)]
        programs = STEP_PROGRAMS
    elif kernel == "attend_decode":
        works = [dsa_work.attend_decode_call(
            w, a["live"] * min(a["ctx"] / a["live"], w["topk"]), a["live"])
            for a in _steps(view)]
        programs = STEP_PROGRAMS
    else:
        call = dsa_work.index_chunk_call if kernel == "index_chunk" \
            else dsa_work.attend_chunk_call
        works = [call(w, a["ntok"], a["cached"]) for a in _passes(view)]
        programs = PASS_PROGRAMS
    return _reading(view, kernel, programs, works, w["layers"])


def _share(view, kernel):
    got = reading(view, kernel)
    return None if got is None else got["share"]


def index_decode_roofline_share(view):
    return _share(view, "index_decode")


def attend_decode_roofline_share(view):
    return _share(view, "attend_decode")


def index_chunk_roofline_share(view):
    return _share(view, "index_chunk")


def attend_chunk_roofline_share(view):
    return _share(view, "attend_chunk")
