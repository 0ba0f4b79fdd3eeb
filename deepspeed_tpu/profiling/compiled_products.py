"""Read a compiled TPU program's text for how its matrix products are fed.

The TPU compiler turns every ``dot_general`` into a ``convolution`` inside a
fusion. An operand of that convolution is either a value in memory (a
parameter of the fusion, perhaps bitcast) or a *producer*: a nested ``kLoop``
fusion the MXU's pipeline re-runs tile by tile. A producer of a few adds is
free; one that holds an ``exponential`` and a ``divide`` over a
``[4096, 14336]`` operand made six products a layer of the train step run at
1.4-2.3 times the MXU's own time (PR 44). :func:`product_fusions` lists every
such fusion with what its operands hold and the compiler's own
``estimated_cycles`` beside the product's; ``scripts/train_products_table.py``
prints it for the train step and ``tests/unit/test_chip_compile.py`` guards
it. A fact about a program, never a measurement.
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, List, Tuple

#: bf16 multiply-adds of a v5e's four 128 x 128 MXUs a cycle, as operations:
#: at 1.5 GHz the published 197 TFLOP/s
V5E_FLOPS_PER_CYCLE = 4 * 128 * 128 * 2

_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = (\(.*?\)|\S+) ([\w-]+)\((.*?)\)(?:, |$)")
_SHAPE = re.compile(r"\w+\[([\d,]*)\]")
_CALLS = re.compile(r"calls=%([^,\s}]+)")
_SCOPE = re.compile(r'op_name="([^"]*)"')
_CYCLES = re.compile(r'"estimated_cycles":"(\d+)"')
_LABELS = re.compile(r"dim_labels=(\w+)_(\w+)->(\w+)")
_OPERAND = re.compile(r"%([^,\s)]+)")
#: opcodes that move or name a value and compute nothing
_PLAIN = frozenset(("parameter", "constant", "broadcast", "bitcast", "copy",
                    "transpose", "reshape", "convert", "tuple", "iota",
                    "get-tuple-element", "dynamic-slice", "slice"))


@dataclasses.dataclass(frozen=True)
class ProductFusion:
    """One executed fusion around a ``convolution``."""
    name: str                          # the fusion instruction, as a trace names it
    scope: str                         # the product's ``op_name``
    out_dims: Tuple[int, ...]          # the product's own result
    flops: int
    estimated_cycles: int              # the compiler's, for the whole fusion
    producers: Tuple[Tuple[str, ...], ...]   # per operand: the opcodes a
    #                                    producer fusion computes, () if a value
    epilogue: Tuple[str, ...]          # what else the fusion computes

    @property
    def ratio(self) -> float:
        """The fusion's estimated cycles over the product's own at the
        MXU's rate (1.00: the product alone)."""
        return self.estimated_cycles * V5E_FLOPS_PER_CYCLE / max(self.flops, 1)


def _computations(text: str) -> Dict[str, List[str]]:
    bodies, name = {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            name = m.group(1)
            bodies[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            bodies[name].append(line)
    return bodies


def _dims(shape: str) -> Tuple[int, ...]:
    m = _SHAPE.match(shape)
    return tuple(int(d) for d in m.group(1).split(",") if d) if m else ()


def _computed(bodies, name) -> set:
    """Opcodes the computation ``name`` computes, nested fusions included."""
    ops = set()
    for line in bodies.get(name, ()):
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        if m.group(3) == "fusion":
            ops |= _computed(bodies, _CALLS.search(line).group(1))
        elif m.group(3) not in _PLAIN:
            ops.add(m.group(3))
    return ops


def product_fusions(text: str) -> List[ProductFusion]:
    """Every fusion of ``compiled.as_text()`` that runs by itself and holds a
    ``convolution``, in the text's order."""
    bodies = _computations(text)
    nested = {_CALLS.search(line).group(1)
              for body in bodies.values() for line in body
              if " fusion(" in line and _CALLS.search(line)}
    out = []
    for comp, body in bodies.items():
        if comp in nested:
            continue
        for line in body:
            m = _INSTRUCTION.match(line)
            if not m or m.group(3) != "fusion":
                continue
            found = _product(bodies, _CALLS.search(line).group(1))
            if found is None:
                continue
            cycles = _CYCLES.search(line)
            out.append(ProductFusion(
                name=m.group(1), estimated_cycles=int(cycles.group(1))
                if cycles else 0, **found))
    return out


def _product(bodies, comp):
    inside = {}
    for line in bodies[comp]:
        m = _INSTRUCTION.match(line)
        if m:
            inside[m.group(1)] = (m.group(2), m.group(3), m.group(4), line)
    convs = [v for v in inside.values() if v[1] == "convolution"]
    if not convs:
        return None
    shape, _, operands, line = convs[0]
    producers, shapes = [], []
    for operand in _OPERAND.findall(operands)[:2]:
        oshape, op, args, oline = inside[operand]
        # a value may reach the product through a bitcast or a copy
        while op in ("bitcast", "copy", "transpose", "reshape"):
            _, op, args, oline = inside[_OPERAND.findall(args)[0]]
        shapes.append(_dims(oshape))
        producers.append(tuple(sorted(_computed(
            bodies, _CALLS.search(oline).group(1)))) if op == "fusion" else ())
    lhs, _, _ = _LABELS.search(line).groups()
    # a product whose result is laid out by heads comes as a windowed
    # convolution over padding: the work is still rows x contracted x columns
    contracted = shapes[0][lhs.index("f")]
    out_dims = _dims(shape)
    scope = _SCOPE.search(line)
    epilogue = {v[1] for v in inside.values()
                if v[1] not in _PLAIN and v[1] not in ("convolution", "fusion")}
    return dict(scope=scope.group(1) if scope else "", out_dims=out_dims,
                flops=2 * math.prod(out_dims) * contracted,
                producers=tuple(producers), epilogue=tuple(sorted(epilogue)))


def fed_through(fusions, opcode: str) -> List[ProductFusion]:
    """The products an operand of which is a producer that computes
    ``opcode``."""
    return [f for f in fusions if any(opcode in p for p in f.producers)]
