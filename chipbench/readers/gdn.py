"""The two gated delta-rule kernels' shares of their rooflines, read from a
``--trace 2`` capture: each kernel's device time from the trace (its Mosaic
calls under its scope, inside the programs that run it for traffic), what it
had to do from the program's own counts of the rows that were live
(``chipbench/reduce/gdn_work.py``).

- ``gdn_decode_step`` under ``gdn/step`` in the decode-step programs: the
  live rows of the captured steps are the ``live`` argument of the program's
  ``serve/decode/step`` spans (the pipeline's row counter, step by step); a
  call is held to ``rows x bytes a row`` over the chip's HBM rate.
- ``gdn_chunk_scan`` under ``gdn/scan`` in the prefill programs: the prompt
  rows and live chunk slots a pass held in the mean are what the program's
  counters ``serve/pass/prompt_tokens``, ``serve/pass/live_slots`` and
  ``serve/pass/passes`` gained over the capture; a call is held to the larger
  of its operations over the bfloat16 peak and its bytes over the HBM rate.

Only live rows count, and each product once, so a reading is a lower bound.
A reader returns nothing where the capture holds no such call, span or
counter (a program older than they are): the metric is then absent."""

from chipbench.reduce import gdn_work, hlo_names, mla_work, named, xplane

STEP_PROGRAMS = ("jit_serve_decode_step",)
SCAN_PROGRAMS = ("jit_serve_prefill_packed", "jit_serve_paged_pass")


def program_ops(trace, op_names):
    """``(program, op_name, event, self nanoseconds)`` of every operation
    that ran inside an execution of a program ("" where the trace carries no
    ``op_name`` for it)."""
    for dev in trace.devices.values():
        mods, k = dev.modules, 0
        for ev, t in dev.self_times():
            while k + 1 < len(mods) and mods[k + 1].start_ns <= ev.start_ns:
                k += 1
            if mods and mods[k].start_ns <= ev.start_ns <= mods[k].end_ns:
                name = op_names.get(mods[k].name, {}).get(
                    xplane.instruction(ev.name).lstrip("%"), "")
                yield named._program(mods[k].name), name, ev, t


def kernel_calls(trace, op_names, scope, programs):
    """Device nanoseconds of every Mosaic call under ``scope`` inside an
    execution of one of ``programs``."""
    pattern = hlo_names.scope_pattern(scope)
    return [t for prog, name, ev, t in program_ops(trace, op_names)
            if prog.startswith(programs) and xplane.is_mosaic(ev.name)
            and pattern.search(name)]


def _widths(view):
    try:
        return gdn_work.widths(view["config"])
    except KeyError:
        return None


def step_reading(view):
    """``mla_work.roofline``'s reading of the decode kernel, or None."""
    w, capture = _widths(view), view.get("capture")
    if w is None or capture is None or not view.get("op_names"):
        return None
    calls = kernel_calls(view["trace"], view["op_names"], "gdn/step",
                         STEP_PROGRAMS)
    live = [r[5]["live"] for r in capture.records
            if r[1] == "serve/decode/step" and r[5] and "live" in r[5]
            and r[2] >= capture.start_ns and r[3] <= capture.stop_ns]
    if not calls or not live:
        return None
    rows = sum(live) / len(live)
    seconds = sum(calls) / len(calls) * 1e-9
    flops, bytes_ = gdn_work.decode_call(
        rows, w["key_heads"], w["value_heads"], w["d_key"], w["d_value"],
        w["d_conv"])
    return dict(mla_work.roofline(flops, bytes_, seconds, view["peaks"]),
                calls=len(calls), us_a_call=seconds * 1e6, rows=rows,
                bytes_a_call=bytes_)


def scan_reading(view):
    """``mla_work.roofline``'s reading of the chunked scan, or None."""
    w, capture = _widths(view), view.get("capture")
    if w is None or capture is None or not view.get("op_names"):
        return None
    calls = kernel_calls(view["trace"], view["op_names"], "gdn/scan",
                         SCAN_PROGRAMS)
    passes = capture.counters.get("serve/pass/passes")
    if not calls or not passes:
        return None
    tokens = capture.counters.get("serve/pass/prompt_tokens", 0.0) / passes
    slots = capture.counters.get("serve/pass/live_slots", 0.0) / passes
    if not tokens:
        return None
    seconds = sum(calls) / len(calls) * 1e-9
    flops, bytes_ = gdn_work.scan_call(
        tokens, slots, w["key_heads"], w["value_heads"], w["d_key"],
        w["d_value"], w["chunk"])
    return dict(mla_work.roofline(flops, bytes_, seconds, view["peaks"]),
                calls=len(calls), us_a_call=seconds * 1e6, tokens=tokens,
                slots=slots, flops_a_call=flops, bytes_a_call=bytes_)


def step_roofline_share(view):
    reading = step_reading(view)
    return None if reading is None else reading["share"]


def scan_roofline_share(view):
    reading = scan_reading(view)
    return None if reading is None else reading["share"]
