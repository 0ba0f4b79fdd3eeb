"""The jaxlint rule set.

Every rule targets a hazard this tree has actually hit (or statically carries):

====== ==============================================================
JL001  wall-clock deltas around async-dispatched work with no sync
JL002  constant PRNG keys baked into library code
JL003  donated-buffer reuse after a ``donate_argnums`` call
JL004  Python control flow on tracer values inside a jitted body
JL005  PartitionSpec/collective axis names no Mesh declares
JL007  blocking host fetches inside configured hot-path modules
JL008  tracer spans enclosing a blocking fetch in hot-path modules
====== ==============================================================

Rules are registered in ``RULE_REGISTRY`` via ``@register``; adding a rule is
one class with ``rule_id``/``summary``/``default_options`` and a
``check(mod, options)`` generator (docs/JAXLINT.md walks through it).
"""

from __future__ import annotations

import ast
from typing import Any, Dict, Iterable, Iterator, List, Optional, Set, Tuple, Type

from deepspeed_tpu.tools.jaxlint.core import (Finding, SourceModule, call_name,
                                              unparse)

RULE_REGISTRY: Dict[str, Type["Rule"]] = {}


def register(cls: Type["Rule"]) -> Type["Rule"]:
    RULE_REGISTRY[cls.rule_id] = cls
    return cls


class Rule:
    rule_id: str = ""
    summary: str = ""
    default_options: Dict[str, Any] = {}

    def check(self, mod: SourceModule, options: Dict[str, Any]) -> Iterator[Finding]:
        raise NotImplementedError


# --------------------------------------------------------------------------- #
# shared helpers
# --------------------------------------------------------------------------- #

_CLOCK_CALLS = {"time.time", "time.perf_counter", "time.monotonic"}


def _is_clock_call(mod: SourceModule, node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and mod.resolve(call_name(node)) in _CLOCK_CALLS)


def _scope_nodes(scope: ast.AST) -> List[ast.AST]:
    """Every AST node belonging to one scope, NOT descending into nested
    function/class/lambda definitions (they are their own scopes)."""
    out: List[ast.AST] = []
    stack: List[ast.AST] = list(ast.iter_child_nodes(scope))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef, ast.Lambda)):
            continue
        out.append(node)
        stack.extend(ast.iter_child_nodes(node))
    return out


def _calls_in_scope(scope: ast.AST) -> List[ast.Call]:
    return [n for n in _scope_nodes(scope) if isinstance(n, ast.Call)]


def _string_constants(node: ast.AST) -> Iterator[Tuple[ast.Constant, str]]:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
            yield sub, sub.value


# --------------------------------------------------------------------------- #
# JL001 — untimed async dispatch
# --------------------------------------------------------------------------- #

@register
class UntimedAsyncDispatch(Rule):
    """``time.time()`` deltas around dispatched work with no ``block_until_ready``.

    jax dispatch is asynchronous: ``t0 = time.time(); y = f(x); dt = time.time()
    - t0`` measures how fast Python *enqueued* the work, not how fast the device
    ran it. A sync point (``block_until_ready`` & friends) must sit between the
    timed region's work and the closing clock read."""

    rule_id = "JL001"
    summary = "wall-clock delta around async dispatch without a sync point"
    default_options = {
        # a call whose final name segment lands here counts as a sync point
        "sync_calls": ["block_until_ready", "effects_barrier", "device_get",
                       "_sync", "_drain", "asarray", "sync", "item", "tolist",
                       "fetch_to_host"],
        # calls that cannot dispatch device work (timing them is fine)
        "benign_calls": ["time", "perf_counter", "monotonic", "print", "len",
                         "int", "float", "str", "min", "max", "range", "append",
                         "format", "join", "log", "info", "debug", "warning"],
    }

    def check(self, mod, options):
        sync_names = set(options["sync_calls"])
        benign = set(options["benign_calls"])
        for scope in mod.functions():
            nodes = _scope_nodes(scope)
            # clock-valued names: t0 = time.time() (a name may be re-stamped;
            # a delta's window starts at the LATEST assignment before it)
            clock_names: Dict[str, List[int]] = {}
            for node in nodes:
                if isinstance(node, ast.Assign) and _is_clock_call(mod, node.value):
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            clock_names.setdefault(tgt.id, []).append(node.lineno)
            deltas: List[Tuple[int, int, int]] = []  # (window_start, line, col)
            for node in nodes:
                if not (isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub)):
                    continue
                starts = []
                for side in (node.left, node.right):
                    if _is_clock_call(mod, side):
                        starts.append(node.lineno)
                    elif isinstance(side, ast.Name) and side.id in clock_names:
                        stamps = [ln for ln in clock_names[side.id]
                                  if ln < node.lineno]
                        if stamps:
                            starts.append(max(stamps))
                if starts:
                    deltas.append((min(starts), node.lineno, node.col_offset))
            for start, line, col in deltas:
                significant = synced = False
                for call in _calls_in_scope(scope):
                    if not (start <= call.lineno <= line):
                        continue
                    name = call_name(call)
                    last = name.split(".")[-1] if name else ""
                    if last in sync_names:
                        synced = True
                    elif last and last not in benign:
                        significant = True
                if significant and not synced:
                    yield Finding(
                        self.rule_id, mod.path, line, col,
                        "wall-clock delta times dispatch, not execution: no "
                        "sync point (block_until_ready) between the timed "
                        "work and the clock read")


# --------------------------------------------------------------------------- #
# JL002 — constant PRNG keys
# --------------------------------------------------------------------------- #

@register
class ConstantPRNGKey(Rule):
    """``jax.random.PRNGKey(<literal>)`` in library code.

    A constant key makes every call site draw the same stream — dropout masks
    repeat across layers and runs, init becomes silently correlated. Library
    code must thread an ``rng`` parameter (default it through
    ``deepspeed_tpu.utils.rng.default_rng()``)."""

    rule_id = "JL002"
    summary = "constant PRNG key baked into library code"
    default_options = {
        # path substrings where constant keys are fine (tests pin seeds)
        "allow_paths": ["/tests/"],
    }

    def check(self, mod, options):
        import os as _os
        norm = mod.path.replace("\\", "/")
        base = _os.path.basename(norm)
        if base.startswith("test_") or base.startswith("conftest"):
            return
        if any(pat in norm for pat in options["allow_paths"]):
            return
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = mod.resolve(call_name(node))
            if not name.endswith("PRNGKey") and not name.endswith("random.key"):
                continue
            seed_args = list(node.args[:1]) + [kw.value for kw in node.keywords
                                               if kw.arg == "seed"]
            if any(isinstance(a, ast.Constant) and isinstance(a.value, int)
                   for a in seed_args):
                yield Finding(
                    self.rule_id, mod.path, node.lineno, node.col_offset,
                    f"constant PRNG key {unparse(node)}: thread an rng "
                    "parameter (utils.rng.default_rng) instead of baking a "
                    "seed into library code")


# --------------------------------------------------------------------------- #
# JL003 — donated-buffer reuse
# --------------------------------------------------------------------------- #

def _donated_positions(call: ast.Call, mod: SourceModule) -> Optional[Set[int]]:
    """If ``call`` is ``jax.jit(..., donate_argnums=...)`` with literal
    positions, return them (resolving through import aliases)."""
    if mod.resolve(call_name(call)) not in {"jax.jit", "jit"}:
        return None
    for kw in call.keywords:
        if kw.arg != "donate_argnums":
            continue
        val = kw.value
        if isinstance(val, ast.Constant) and isinstance(val.value, int):
            return {val.value}
        if isinstance(val, (ast.Tuple, ast.List)):
            out = set()
            for elt in val.elts:
                if isinstance(elt, ast.Constant) and isinstance(elt.value, int):
                    out.add(elt.value)
                else:
                    return None  # dynamic positions: can't reason statically
            return out
    return None


@register
class DonatedBufferReuse(Rule):
    """Reading a buffer again after passing it at a ``donate_argnums`` position.

    Donation hands the buffer to XLA for reuse; the Python reference keeps
    pointing at freed (or, on jaxlib 0.4.x CPU, heap-corrupting — see PR 1)
    memory. Two checks:

    1. the donated expression is *loaded* again later in the same function
       without an intervening rebind;
    2. the donated argument aliases longer-lived state (``x = obj.attr`` then
       ``f(x)``) and ``obj.attr`` is never rebound afterwards — the holder
       object keeps a stale reference after the function returns.
    """

    rule_id = "JL003"
    summary = "donated buffer read (or left referenced) after donation"
    default_options = {
        # extra callables known to donate (AOT executables whose jit-time
        # donation is invisible at the call site), name -> positions
        "assume_donated": {},
    }

    # -- module pass: which names/attrs hold donating callables ----------- #
    def _donating_callables(self, mod: SourceModule,
                            extra: Dict[str, Iterable[int]]) -> Dict[str, Set[int]]:
        donating: Dict[str, Set[int]] = {k: set(v) for k, v in extra.items()}
        for node in ast.walk(mod.tree):
            # name = jax.jit(f, donate_argnums=...)   /  self._f = jax.jit(...)
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                pos = _donated_positions(node.value, mod)
                if pos:
                    for tgt in node.targets:
                        if isinstance(tgt, (ast.Name, ast.Attribute)):
                            donating[unparse(tgt)] = pos
            # @functools.partial(jax.jit, donate_argnums=...) def f(...)
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if isinstance(dec, ast.Call) and call_name(dec).endswith("partial") \
                            and dec.args and mod.resolve(unparse(dec.args[0])) \
                            in {"jax.jit", "jit"}:
                        fake = ast.Call(func=dec.args[0], args=[],
                                        keywords=dec.keywords)
                        ast.copy_location(fake, dec)
                        pos = _donated_positions(fake, mod)
                        if pos:
                            donating[node.name] = pos
        return donating

    def check(self, mod, options):
        donating = self._donating_callables(mod, options["assume_donated"])
        if not donating:
            return
        for scope in mod.functions():
            yield from self._check_scope(mod, scope, donating)

    def _check_scope(self, mod, scope, donating):
        nodes = _scope_nodes(scope)
        # alias map: local name -> the name-chain expr it was read from
        aliases: Dict[str, str] = {}
        for stmt in nodes:
            if isinstance(stmt, ast.Assign) and isinstance(
                    stmt.value, (ast.Name, ast.Attribute, ast.Subscript)):
                for tgt in stmt.targets:
                    if isinstance(tgt, ast.Name):
                        aliases[tgt.id] = unparse(stmt.value)
            if isinstance(stmt, ast.Assign) and isinstance(stmt.value, ast.Tuple):
                tgts = stmt.targets[0].elts if (
                    stmt.targets and isinstance(stmt.targets[0], ast.Tuple)) else []
                for tgt, val in zip(tgts, stmt.value.elts):
                    if isinstance(tgt, ast.Name) and isinstance(
                            val, (ast.Name, ast.Attribute, ast.Subscript)):
                        aliases[tgt.id] = unparse(val)

        stores: List[Tuple[int, str]] = []          # (line, expr stored to)
        loads: List[Tuple[int, str]] = []           # (line, expr loaded)
        method_calls: List[Tuple[int, str]] = []    # (line, receiver expr)
        for node in nodes:
            if isinstance(node, (ast.Name, ast.Attribute, ast.Subscript)):
                expr = unparse(node)
                if isinstance(getattr(node, "ctx", None), ast.Store):
                    stores.append((node.lineno, expr))
                elif isinstance(getattr(node, "ctx", None), ast.Load):
                    loads.append((node.lineno, expr))
            if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
                method_calls.append((node.lineno, unparse(node.func.value)))

        for call in _calls_in_scope(scope):
            target = unparse(call.func)
            positions = donating.get(target) or donating.get(aliases.get(target, ""))
            if not positions:
                continue
            for pos in sorted(positions):
                if pos >= len(call.args):
                    continue
                arg = call.args[pos]
                if not isinstance(arg, (ast.Name, ast.Attribute, ast.Subscript)):
                    continue
                expr = unparse(arg)
                line = call.lineno
                # loads inside the (possibly multi-line) call are the donated
                # argument itself, not a re-read
                end = getattr(call, "end_lineno", None) or line
                # check 1: re-read after donation, before any rebind
                rebind_lines = [ln for ln, e in stores if e == expr and ln >= line]
                next_rebind = min(rebind_lines) if rebind_lines else None
                for ln, e in loads:
                    if e == expr and ln > end and (next_rebind is None
                                                   or ln < next_rebind):
                        yield Finding(
                            self.rule_id, mod.path, ln, 0,
                            f"'{expr}' was donated to '{target}' on line "
                            f"{line} and is read again here — donated buffers "
                            "are freed (or aliased) by XLA")
                        break
                # check 2: donated value aliases longer-lived state that is
                # never rebound after the call
                origin = aliases.get(expr) if isinstance(arg, ast.Name) else None
                if origin and ("." in origin or "[" in origin):
                    rebound = any(e == origin and ln >= line for ln, e in stores)
                    touched = any(recv == origin or origin.startswith(recv + ".")
                                  or origin.startswith(recv + "[")
                                  for ln, recv in method_calls if ln > line)
                    if not rebound and not touched:
                        yield Finding(
                            self.rule_id, mod.path, line, call.col_offset,
                            f"'{expr}' (read from '{origin}') was donated to "
                            f"'{target}' but '{origin}' still references the "
                            "donated buffers — rebind it after the call")


# --------------------------------------------------------------------------- #
# JL004 — Python control flow on tracers
# --------------------------------------------------------------------------- #

_SHAPE_ATTRS = {"shape", "ndim", "dtype", "size", "aval", "sharding", "weak_type"}
_HOST_FNS = {"len", "isinstance", "hasattr", "getattr", "callable", "type", "id"}


def _tracer_names_in_test(test: ast.AST, traced: Set[str]) -> List[ast.Name]:
    """Name nodes in a branch test that read a traced value *as a value*
    (``x.shape``-style static metadata and ``len``/``isinstance`` don't trace)."""
    hits: List[ast.Name] = []

    def rec(node: ast.AST) -> None:
        if isinstance(node, ast.Attribute) and node.attr in _SHAPE_ATTRS:
            return
        if isinstance(node, ast.Call):
            name = call_name(node)
            if name.split(".")[-1] in _HOST_FNS:
                return
            for arg in node.args:
                rec(arg)
            for kw in node.keywords:
                rec(kw.value)
            return
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load) \
                and node.id in traced:
            hits.append(node)
            return
        for child in ast.iter_child_nodes(node):
            rec(child)

    rec(test)
    return hits


@register
class TracerControlFlow(Rule):
    """Python ``if``/``while`` on tracer values inside a jitted body.

    Under ``jax.jit`` the arguments are tracers; ``if x > 0`` forces a
    concrete bool — a TracerBoolConversionError at best, a silent recompile
    per branch at worst. Use ``lax.cond``/``lax.select``/``jnp.where``."""

    rule_id = "JL004"
    summary = "Python control flow on a tracer inside a jitted function"
    default_options = {}

    def _jitted_defs(self, mod: SourceModule) -> List[Tuple[ast.AST, Set[str]]]:
        defs_by_name: Dict[str, ast.AST] = {}
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs_by_name.setdefault(node.name, node)
        out: List[Tuple[ast.AST, Set[str]]] = []
        seen: Set[ast.AST] = set()

        def statics_from_call(call: ast.Call) -> Tuple[Set[int], Set[str]]:
            nums: Set[int] = set()
            names: Set[str] = set()
            for kw in call.keywords:
                if kw.arg == "static_argnums":
                    for c, _v in [(e, e.value) for e in ast.walk(kw.value)
                                  if isinstance(e, ast.Constant)
                                  and isinstance(e.value, int)]:
                        nums.add(c.value)
                if kw.arg == "static_argnames":
                    for _c, v in _string_constants(kw.value):
                        names.add(v)
            return nums, names

        def add(fn: ast.AST, call: Optional[ast.Call]) -> None:
            if fn in seen:
                return
            seen.add(fn)
            nums, names = statics_from_call(call) if call else (set(), set())
            params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
            traced = {p for i, p in enumerate(params)
                      if i not in nums and p not in names and p != "self"}
            out.append((fn, traced))

        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if mod.resolve(unparse(dec)) in {"jax.jit", "jit"}:
                        add(node, None)
                    elif isinstance(dec, ast.Call):
                        target = mod.resolve(call_name(dec))
                        if target in {"jax.jit", "jit"}:
                            add(node, dec)
                        elif target.endswith("partial") and dec.args and \
                                mod.resolve(unparse(dec.args[0])) in {"jax.jit", "jit"}:
                            add(node, dec)
            if isinstance(node, ast.Call) \
                    and mod.resolve(call_name(node)) in {"jax.jit", "jit"} \
                    and node.args and isinstance(node.args[0], ast.Name) \
                    and node.args[0].id in defs_by_name:
                add(defs_by_name[node.args[0].id], node)
        return out

    def check(self, mod, options):
        for fn, traced in self._jitted_defs(mod):
            if not traced:
                continue
            for stmt in _scope_nodes(fn):
                if not isinstance(stmt, (ast.If, ast.While)):
                    continue
                hits = _tracer_names_in_test(stmt.test, traced)
                if hits:
                    kind = "while" if isinstance(stmt, ast.While) else "if"
                    yield Finding(
                        self.rule_id, mod.path, stmt.lineno, stmt.col_offset,
                        f"Python `{kind}` on traced value "
                        f"'{hits[0].id}' inside jitted '{fn.name}': use "
                        "lax.cond/lax.while_loop/jnp.where")


# --------------------------------------------------------------------------- #
# JL005 — undeclared mesh axis names
# --------------------------------------------------------------------------- #

_COLLECTIVES = {"psum", "pmean", "pmax", "pmin", "ppermute", "pshuffle",
                "all_gather", "all_to_all", "axis_index", "psum_scatter"}


@register
class UndeclaredMeshAxis(Rule):
    """String axis names used in PartitionSpec / collectives that no Mesh in
    the module (nor the configured global axis registry) declares.

    A typo'd axis name fails only when the program finally traces under a
    mesh — often on the TPU, minutes into a run. Checked statically instead.
    Modules that build no Mesh and have no ``known_axes`` configured are
    skipped (their axes come from elsewhere)."""

    rule_id = "JL005"
    summary = "PartitionSpec/collective axis name no Mesh declares"
    default_options = {
        "known_axes": [],
    }

    def _mesh_axes(self, mod: SourceModule) -> Tuple[Set[str], bool]:
        axes: Set[str] = set()
        declared = False
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = mod.resolve(call_name(node))
            if name.split(".")[-1] not in {"Mesh", "make_mesh"}:
                continue
            declared = True
            sources: List[ast.AST] = []
            if len(node.args) >= 2:
                sources.append(node.args[1])
            for kw in node.keywords:
                if kw.arg == "axis_names":
                    sources.append(kw.value)
            for src in sources:
                for _node, val in _string_constants(src):
                    axes.add(val)
        return axes, declared

    def check(self, mod, options):
        known = set(options["known_axes"])
        mesh_axes, declared = self._mesh_axes(mod)
        known |= mesh_axes
        if not known and not declared:
            return
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = mod.resolve(call_name(node))
            tail = name.split(".")[-1]
            sources: List[ast.AST] = []
            if tail in {"PartitionSpec", "P"}:
                sources.extend(node.args)
                sources.extend(kw.value for kw in node.keywords)
            elif tail in _COLLECTIVES:
                # axis_index takes the axis name as its FIRST argument; the
                # other collectives take (operand, axis_name, ...)
                sources.extend(node.args[0:1] if tail == "axis_index"
                               else node.args[1:2])
                sources.extend(kw.value for kw in node.keywords
                               if kw.arg in {"axis_name", "axis"})
            for src in sources:
                for const, val in _string_constants(src):
                    if val not in known:
                        yield Finding(
                            self.rule_id, mod.path, const.lineno,
                            const.col_offset,
                            f"axis name '{val}' is not declared by any Mesh "
                            "in this module nor in jaxlint's known_axes")


# --------------------------------------------------------------------------- #
# JL007 — blocking host fetch in a hot-path module
# --------------------------------------------------------------------------- #

@register
class HotPathHostFetch(Rule):
    """Blocking device->host fetches inside modules marked hot-path.

    The v2 serving loop is engineered so ONE drain point per decode step
    fetches one int32 token row; a stray ``np.asarray(logits)`` / ``.item()``
    / ``jax.device_get(...)`` in that path silently re-serialises the host on
    the device (and, through a remote runtime, re-adds an RTT per token) —
    the regression class ``inference/v2/pipeline.py`` was built to remove.
    Inert unless the config
    lists ``hot_paths`` substrings (``.jaxlint.json``), so only modules that
    opted into hot-path discipline are policed; the intentional drain carries
    an inline ``# jaxlint: disable=JL007``.

    Heuristics (static — no type info):

    - ``jax.device_get(...)`` always blocks: flagged.
    - ``np.asarray(x)`` / ``np.array(x)`` with a SINGLE positional argument
      and no ``dtype`` is how this tree drains device arrays; host-side
      conversions say ``np.asarray(x, np.int32)``. Single-arg forms are
      flagged — give host conversions an explicit dtype (cheap and
      self-documenting) or suppress inline.
    - ``.item()`` / ``.tolist()`` force a transfer on jax arrays: flagged.
    """

    rule_id = "JL007"
    summary = "blocking host fetch inside a hot-path module"
    default_options = {
        # path substrings whose modules are hot-path; empty = rule inert
        "hot_paths": [],
        # zero-arg methods that force a device->host transfer
        "fetch_methods": ["item", "tolist"],
    }

    def check(self, mod, options):
        norm = mod.path.replace("\\", "/")
        if not any(pat in norm for pat in options["hot_paths"]):
            return
        fetch_methods = set(options["fetch_methods"])
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = mod.resolve(call_name(node))
            if name == "jax.device_get":
                # (block_until_ready is deliberately NOT flagged: a sync
                # without a transfer is how warmup/timing code is SUPPOSED
                # to wait, and JL001 already polices its absence)
                yield Finding(
                    self.rule_id, mod.path, node.lineno, node.col_offset,
                    "jax.device_get() blocks the host in a hot-path module "
                    "— route the fetch through the engine drain point "
                    "(fetch_to_host) or suppress the intentional drain inline")
            elif name in {"numpy.asarray", "numpy.array"}:
                has_dtype = (len(node.args) > 1
                             or any(kw.arg == "dtype" for kw in node.keywords))
                if len(node.args) == 1 and not has_dtype:
                    yield Finding(
                        self.rule_id, mod.path, node.lineno, node.col_offset,
                        f"{unparse(node.func)}(x) with no dtype may be a "
                        "blocking device fetch in a hot-path module — use "
                        "the engine drain point (fetch_to_host), or give a "
                        "host-side conversion an explicit dtype")
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr in fetch_methods
                  and not node.args and not node.keywords
                  and not isinstance(node.func.value, ast.Constant)):
                yield Finding(
                    self.rule_id, mod.path, node.lineno, node.col_offset,
                    f".{node.func.attr}() forces a device->host transfer in "
                    "a hot-path module — drain through fetch_to_host (or "
                    "suppress if the receiver is host data)")


# --------------------------------------------------------------------------- #
# JL008 — tracer span enclosing a blocking fetch
# --------------------------------------------------------------------------- #

@register
class SpanEnclosedBlockingFetch(Rule):
    """``with tracer.span(...)`` bodies in hot-path modules must not contain
    a blocking device->host fetch outside the policed drain names.

    The span tracer (``monitor/trace.py``) exists to make the async
    pipelines' overlap auditable WITHOUT perturbing it: spans read only
    ``perf_counter``. The failure mode this rule guards is instrumentation
    drift — someone wraps a phase in a span and, "while they're in there",
    materialises a value for the span's args or a log line. That quietly
    reintroduces the per-step host sync the pipelines removed, and the
    timeline then *hides* the regression (the sync cost is inside a
    legitimate-looking span). Flagged inside span bodies, same fetch
    heuristics as JL007: ``jax.device_get``, single-arg ``np.asarray``/
    ``np.array`` without a dtype, ``.item()``/``.tolist()``. Calls whose
    final name segment is a policed drain (``drain_calls``, default
    ``fetch_to_host``) are allowed — attributing the drain is exactly what
    spans are for. Nested function/lambda bodies are skipped (work submitted
    to an executor from inside a span is not synchronously enclosed)."""

    rule_id = "JL008"
    summary = "tracer span encloses a blocking host fetch"
    default_options = {
        # path substrings whose modules are policed; empty = rule inert
        "hot_paths": [],
        # call names (final segment) that ARE the sanctioned drain points
        "drain_calls": ["fetch_to_host"],
        # zero-arg methods that force a device->host transfer
        "fetch_methods": ["item", "tolist"],
    }

    def _span_withs(self, mod: SourceModule) -> Iterator[ast.With]:
        for node in ast.walk(mod.tree):
            if not isinstance(node, (ast.With, ast.AsyncWith)):
                continue
            for item in node.items:
                ce = item.context_expr
                if isinstance(ce, ast.Call) \
                        and call_name(ce).split(".")[-1] == "span":
                    yield node
                    break

    @staticmethod
    def _body_nodes(with_node: ast.With) -> List[ast.AST]:
        """Nodes lexically inside the with-body, not descending into nested
        function/class/lambda scopes (their execution isn't enclosed)."""
        out: List[ast.AST] = []
        stack: List[ast.AST] = list(with_node.body)
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef, ast.Lambda)):
                continue
            out.append(node)
            stack.extend(ast.iter_child_nodes(node))
        return out

    def check(self, mod, options):
        norm = mod.path.replace("\\", "/")
        if not any(pat in norm for pat in options["hot_paths"]):
            return
        drains = set(options["drain_calls"])
        fetch_methods = set(options["fetch_methods"])
        for with_node in self._span_withs(mod):
            for node in self._body_nodes(with_node):
                if not isinstance(node, ast.Call):
                    continue
                raw = call_name(node)
                if raw.split(".")[-1] in drains:
                    continue
                name = mod.resolve(raw)
                msg = None
                if name == "jax.device_get":
                    msg = ("jax.device_get() inside a tracer span — the span "
                           "would hide a hot-path host sync; route through "
                           "the policed drain (fetch_to_host) or move the "
                           "fetch out of the span")
                elif name in {"numpy.asarray", "numpy.array"}:
                    has_dtype = (len(node.args) > 1
                                 or any(kw.arg == "dtype"
                                        for kw in node.keywords))
                    if len(node.args) == 1 and not has_dtype:
                        msg = (f"{unparse(node.func)}(x) with no dtype inside "
                               "a tracer span may be a blocking device fetch "
                               "— drain through fetch_to_host (outside the "
                               "span) or give a host conversion an explicit "
                               "dtype")
                elif (isinstance(node.func, ast.Attribute)
                      and node.func.attr in fetch_methods
                      and not node.args and not node.keywords
                      and not isinstance(node.func.value, ast.Constant)):
                    msg = (f".{node.func.attr}() inside a tracer span forces "
                           "a device->host transfer — move it out of the "
                           "span or route through the policed drain")
                if msg:
                    yield Finding(self.rule_id, mod.path, node.lineno,
                                  node.col_offset, msg)
