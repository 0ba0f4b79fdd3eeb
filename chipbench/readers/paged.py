"""The paged attention kernels' shares of their rooflines, read from a
``--trace 2`` capture: a kernel's device time from the trace (its Mosaic
calls under its scope, inside the programs that run it for traffic), what it
had to do from what the program says its rows held — the arguments of the
``serve/decode/step`` and ``serve/prefill/pass`` spans inside the capture
(``deepspeed_tpu/inference/v2/pipeline.py::rows_held``,
``serving/frontend.py::_pass``) — through ``chipbench/reduce/kv_work.py`` and
``mla_work.py``.

A layer loop runs one call many times an execution, and a model may run two
kinds of it (full and windowed layers), so time and work are both taken an
EXECUTION of the program: the calls' device time summed, over the whole
executions the trace holds; the mean captured record's work, summed over the
layers that attend. The share is the least time the chip could take for that
work (``mla_work.roofline``: the larger of operations over the bfloat16 peak
and bytes over the HBM rate) over the time the kernels took.

- ``decode_roofline_share``: ``paged_decode_sidebuf`` (and whatever other
  ``paged_decode*`` rung a step takes) under ``attn/attn_full``,
  ``attn/attn_window`` and ``attn/cca/attn_full`` in the decode-step
  programs. Bytes bind it in every configuration (``kv_work``): the bytes
  its steps' attention had to read over the time its kernels took to read
  them, over the HBM rate.
- ``chunk_roofline_share``: ``paged_chunk`` in the paged-pass programs,
  against the passes of ``kind == "paged"``. A capture that holds no such
  pass, or no such call, gives nothing.
- ``mla_decode_roofline_share``: ``mla_decode`` under ``attn/mla/decode`` in
  the decode-step programs; a call is linear in a row's context, so the
  step's rows and the sum of their contexts are all it needs.

Only live rows count, and each product once, so a reading is a lower bound.
A reader returns nothing where the view has no capture, no such call or no
record with the arguments (a program older than they are): the metric is
then absent."""

from chipbench.reduce import hlo_names, kv_work, mla_work, named, xplane

STEP_PROGRAMS = ("jit_serve_decode_step",)
PASS_PROGRAMS = ("jit_serve_paged_pass",)
DECODE_SCOPE = r"attn_(?:full|window)/paged_decode\w*"
CHUNK_SCOPE = r"attn_(?:full|window)/paged_chunk\w*"
MLA_DECODE_SCOPE = r"mla/decode/mla_decode"


def calls_an_execution(trace, op_names, scope, programs):
    """``(nanoseconds, executions)``: the device time of every Mosaic call
    under ``scope`` (a regular expression over the components of an
    ``op_name``) inside a whole execution of one of ``programs``, and how
    many such executions there are. A chip's first and last execution are
    left out where it has three or more, as in ``xplane.module_table``: the
    trace clips them."""
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
            if mine[k].start_ns <= ev.start_ns and xplane.is_mosaic(ev.name) \
                    and pattern.search(op_names.get(mine[k].name, {}).get(
                        xplane.instruction(ev.name).lstrip("%"), "")):
                total += t
    return total, runs


def records(view, name, *needed):
    """The arguments of the program's ``name`` spans that lie whole inside
    the captured interval and carry every argument of ``needed``."""
    capture = view["capture"]
    return [r[5] for r in capture.records
            if r[0] == "X" and r[1] == name and r[5]
            and all(k in r[5] for k in needed)
            and r[2] >= capture.start_ns and r[3] <= capture.stop_ns]


def _floor_s(work, peaks):
    """The least seconds the chip could take for ``(operations, bytes)``."""
    reading = mla_work.roofline(work[0], work[1], 1.0, peaks)
    return max(reading["compute_s"], reading["memory_s"])


def _reading(view, scope, programs, floors):
    """``floors``: the least seconds an execution's calls could take, a
    captured record; against the calls' seconds an execution."""
    ns, runs = calls_an_execution(view["trace"], view["op_names"], scope,
                                  programs)
    if not ns or not floors or not sum(floors):
        return None
    floor_s, seconds = sum(floors) / len(floors), ns / runs * 1e-9
    return {"share": 100.0 * floor_s / seconds, "executions": runs,
            "records": len(floors), "kernel_us": seconds * 1e6,
            "floor_us": floor_s * 1e6}


def _kv_widths(view):
    if view.get("capture") is None or not view.get("op_names"):
        return None
    try:
        return kv_work.widths(view["config"])
    except (KeyError, AttributeError):
        return None


def decode_reading(view):
    """The K/V decode kernel's reading, or None: ``share`` in percent, the
    kernels' and the floor's microseconds a step, steps counted."""
    w = _kv_widths(view)
    if w is None:
        return None
    floors = []
    for a in records(view, "serve/decode/step", "live", "ctx", "pages"):
        floor = w["full_layers"] * _floor_s(kv_work.decode_call(
            w, a["live"], a["ctx"], a["pages"]), view["peaks"])
        if w["windowed_layers"]:
            # one window a configuration: a tuple (several) is not read
            if not isinstance(a.get("ctx_window"), int):
                return None
            floor += w["windowed_layers"] * _floor_s(kv_work.decode_call(
                w, a["live"], a["ctx"], a["pages"], a["ctx_window"]),
                view["peaks"])
        floors.append(floor)
    return _reading(view, DECODE_SCOPE, STEP_PROGRAMS, floors)


def chunk_reading(view):
    """The prompt chunk kernel's reading over the captured paged passes, or
    None."""
    w = _kv_widths(view)
    if w is None:
        return None
    floors = []
    for a in records(view, "serve/prefill/pass", "kind", "ntok", "cached"):
        if a["kind"] != "paged":
            continue
        floor = w["full_layers"] * _floor_s(kv_work.chunk_call(
            w, a["ntok"], a["cached"]), view["peaks"])
        if w["windowed_layers"]:
            floor += w["windowed_layers"] * _floor_s(kv_work.chunk_call(
                w, a["ntok"], a["cached"], w["window"]), view["peaks"])
        floors.append(floor)
    return _reading(view, CHUNK_SCOPE, PASS_PROGRAMS, floors)


def mla_decode_reading(view):
    """The latent decode kernel's reading, or None."""
    config = view.get("config", {})
    if view.get("capture") is None or not view.get("op_names") \
            or "kv_lora_rank" not in config:
        return None
    # linear in a row's context: one row of the step's whole context and
    # its other rows empty is the step's rows at their own contexts
    floors = [config["num_hidden_layers"] * _floor_s(mla_work.decode_call(
        [a["ctx"]] + [0] * (a["live"] - 1), config["num_attention_heads"],
        config["kv_lora_rank"], config["qk_rope_head_dim"]), view["peaks"])
        for a in records(view, "serve/decode/step", "live", "ctx")
        if a["live"]]
    return _reading(view, MLA_DECODE_SCOPE, STEP_PROGRAMS, floors)


def _share(reading):
    return None if reading is None else reading["share"]


def decode_roofline_share(view):
    return _share(decode_reading(view))


def chunk_roofline_share(view):
    return _share(chunk_reading(view))


def mla_decode_roofline_share(view):
    return _share(mla_decode_reading(view))
