"""The names the program gave its device work, read out of a profiler trace.

A ``jax.named_scope`` does not show in an ``XLA Ops`` event's name (that is
the instruction's HLO text, ``%closed_call.21 = ... custom-call(...)``), nor
in the event's statistics. It is in the instruction's ``op_name`` metadata
(``jit(step_fn)/jit(main)/transpose(jvp(flash_bwd_dq))/pallas_call``), and the
trace carries that: the plane ``/host:metadata`` holds one event-metadata
entry per program, named like the program's ``XLA Modules`` events
(``jit_step_fn(<fingerprint>)``), with the program's ``HloProto`` as a bytes
statistic (``chipbench/tools/record_scoped_trace.py`` prints the layout;
PERF.md section 3). ``jax.profiler.ProfileData`` does not expose event
metadata, so this file reads the few protobuf fields it needs from the wire
format itself: no dependency beyond Python.

Field numbers (tsl ``xplane.proto``, xla ``hlo.proto``): XSpace.planes=1;
XPlane.name=2, .event_metadata=4 (map: key=1, value=2);
XEventMetadata.name=2, .stats=5; XStat.bytes_value=6; HloProto.hlo_module=1;
HloModuleProto.computations=3; HloComputationProto.instructions=2;
HloInstructionProto.name=1, .metadata=7; OpMetadata.op_name=2.
"""

from __future__ import annotations

import re
from typing import Dict, Iterator, Tuple

METADATA_PLANE = "/host:metadata"


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def fields(buf) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of one message; a length-delimited
    value is a view into ``buf``, a varint an int."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire == 1:
            value = buf[i:i + 8]
            i += 8
        elif wire == 5:
            value = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield number, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _first(buf, number: int):
    for f, _, v in fields(buf):
        if f == number:
            return v
    return None


def _instructions(hlo_proto) -> Dict[str, str]:
    names: Dict[str, str] = {}
    module = _first(hlo_proto, 1)
    if module is None:
        return names
    for f, w, computation in fields(module):
        if f != 3 or w != 2:
            continue
        for cf, cw, instr in fields(computation):
            if cf != 2 or cw != 2:
                continue
            name = op_name = None
            for jf, jw, jv in fields(instr):
                if jf == 1 and jw == 2:
                    name = _text(jv)
                elif jf == 7 and jw == 2:
                    op = _first(jv, 2)
                    op_name = _text(op) if op is not None else None
            if name and op_name:
                names[name] = op_name
    return names


def load(path: str) -> Dict[str, Dict[str, str]]:
    """``{program: {instruction name: op_name}}`` for every program whose HLO
    the trace carries; ``program`` is the name of its ``XLA Modules`` events.
    Empty where the trace has no metadata plane."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for f, w, plane in fields(data):
        if f != 1 or w != 2:
            continue
        name = _first(plane, 2)
        if name is None or _text(name) != METADATA_PLANE:
            continue
        for pf, pw, entry in fields(plane):
            if pf != 4 or pw != 2:
                continue
            meta = _first(entry, 2)
            if meta is None:
                continue
            program = proto = None
            for mf, mw, mv in fields(meta):
                if mf == 2 and mw == 2:
                    program = _text(mv)
                elif mf == 5 and mw == 2:
                    blob = _first(mv, 6)
                    if blob is not None:
                        proto = blob
            if program and proto is not None:
                out[program] = _instructions(proto)
    return out


def scope_pattern(scope: str) -> "re.Pattern":
    """Matches an ``op_name`` that has ``scope`` (a regular expression) as one
    of its components, bare or wrapped by a transformation:
    ``.../flash_fwd/pallas_call``, ``.../transpose(jvp(flash_bwd_dq))/...``."""
    return re.compile(r"(?:^|[/(])(?:" + scope + r")(?:[/)]|$)")
