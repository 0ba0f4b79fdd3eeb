"""Device work by the names the program gave it: jitted programs by their
``XLA Modules`` name (``jit_serve_decode_step``), operations by the
``jax.named_scope`` in their ``op_name`` (``hlo_names.load``), and the time
an asynchronous collective is hidden under other work. All on the plain
:class:`~chipbench.reduce.xplane.Trace`, so the tests can write one by hand.
"""

from __future__ import annotations

import re
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from chipbench.reduce import hlo_names, xplane

OpNames = Dict[str, Dict[str, str]]
_SHAPE = re.compile(r"(?:bf16|f16|f32)\[(\d+),(\d+),(\d+),(\d+)\]")


def _program(event_name: str) -> str:
    return xplane.module_name(event_name)[0]


def program_ms(trace: xplane.Trace, prefix: str) -> Optional[float]:
    """Mean device milliseconds of the executions of the programs whose name
    starts with ``prefix``, whatever program ran most. A chip's first and
    last execution are left out, as in ``xplane.module_table``."""
    rows = [r for r in xplane.module_table(trace)
            if _program(r["module"]).startswith(prefix)]
    calls = sum(r["calls"] for r in rows)
    return sum(r["total_ms"] for r in rows) / calls if calls else None


def program_share(trace: xplane.Trace, prefixes: Sequence[str]
                  ) -> Optional[float]:
    """Device time inside the programs whose name starts with one of
    ``prefixes`` (the busy time of their operations) over all busy time,
    summed over chips; None where no such program ran."""
    picked = busy = 0.0
    found = False
    for dev in trace.devices.values():
        spans = xplane.union(dev.ops)
        busy += xplane.covered_ns(spans)
        mine = xplane.union(m for m in dev.modules if any(
            _program(m.name).startswith(p) for p in prefixes))
        found = found or bool(mine)
        picked += xplane.overlap_ns(spans, mine)
    return picked / busy if found and busy else None


def named_ops(trace: xplane.Trace, op_names: OpNames
              ) -> Iterator[Tuple[xplane.Event, float, str]]:
    """Every operation with its self time and its ``op_name`` ("" where the
    trace does not carry one): an operation belongs to the program whose
    execution it falls in."""
    for dev in trace.devices.values():
        mods, k = dev.modules, 0
        for ev, t in dev.self_times():
            while k + 1 < len(mods) and mods[k + 1].start_ns <= ev.start_ns:
                k += 1
            names = {}
            if mods and mods[k].start_ns <= ev.start_ns <= mods[k].end_ns:
                names = op_names.get(mods[k].name, {})
            yield ev, t, names.get(xplane.instruction(ev.name).lstrip("%"), "")


def scope_share(trace: xplane.Trace, op_names: OpNames, scope: str,
                instructions: Sequence[str] = ()) -> Optional[float]:
    """Self time of the operations under ``scope`` (a regular expression for
    one component of the ``op_name``) over busy time, summed over chips;
    None where no operation carries it. ``instructions`` are prefixes of
    instruction names that count as under the scope too: a kernel XLA itself
    puts in for an operation (``%ragged-dot-none.1``) does not keep the
    ``op_name`` of the operation it stands for."""
    pattern = hlo_names.scope_pattern(scope)
    picked, found = 0.0, False
    for ev, t, name in named_ops(trace, op_names):
        if (name and pattern.search(name)) or any(
                xplane.instruction(ev.name).startswith(p)
                for p in instructions):
            picked += t
            found = True
    busy = sum(xplane.covered_ns(xplane.union(d.ops))
               for d in trace.devices.values())
    return picked / busy if found and busy else None


def scope_calls(trace: xplane.Trace, op_names: OpNames, scope: str
                ) -> List[Tuple[xplane.Event, float]]:
    """The Mosaic calls under ``scope`` with their device nanoseconds."""
    pattern = hlo_names.scope_pattern(scope)
    return [(ev, t) for ev, t, name in named_ops(trace, op_names)
            if name and pattern.search(name) and xplane.is_mosaic(ev.name)]


def first_operand_shape(event_name: str) -> Optional[Tuple[int, ...]]:
    """``(B, H, T, D)`` of the first rank-4 operand in an operation's HLO
    text: q of a flash kernel's call."""
    _, _, args = event_name.partition("custom-call(")
    m = _SHAPE.search(args)
    return tuple(int(x) for x in m.groups()) if m else None


def hidden_collective_share(trace: xplane.Trace) -> Optional[float]:
    """Time an asynchronous collective was in flight while the operations
    line was busy with something else, over the window, on the chip where it
    is largest; None where no collective was in flight."""
    t0, t1 = xplane.window(trace)
    worst = None
    for dev in trace.devices.values():
        flying = xplane.collective_spans(dev)
        if not flying:
            continue
        busy = xplane.overlap_ns(xplane.union(dev.ops), flying)
        own = xplane.overlap_ns(
            xplane.union(e for e in dev.ops if xplane.is_collective(e.name)),
            flying)
        worst = max(worst or 0.0, (busy - own) / (t1 - t0))
    return worst
