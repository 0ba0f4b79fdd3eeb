"""The whole-program model threadlint's rules run over.

Built once per lint from every parsed module (``Program.build``):

- **classes & functions** — every class, method, module function, and
  nested ``def`` gets a :class:`FunctionInfo` under a stable qualname;
- **locks** — attributes (or locals) created through
  ``utils/threads.make_lock("name")`` / ``make_rlock`` / ``make_semaphore``
  carry their declared name; raw ``threading.Lock()`` attributes fall back
  to ``Class.attr``. Lock names are lockdep-style classes: every lock
  minted at one site shares the name;
- **call graph** — conservative resolution of ``self.m()``, same-module
  ``f()``, ``self.attr.m()`` (through attribute types recorded at
  ``self.attr = SomeClass(...)`` sites), and imported-module calls;
- **thread roles** — seeded by ``@thread_role(...)`` / ``# threadlint:
  role=...`` on entry points, by ``Thread(target=..., name="...")`` and by
  executor ``thread_name_prefix``, then propagated caller -> callee to a
  fixpoint. Functions no in-program thread reaches run as ``main`` (the
  client / test thread);
- **held-lock facts** — the lexical ``with``-stack at every call and
  attribute write, plus an interprocedural ``always_held`` (locks held at
  EVERY call site, propagated with set-intersection) so a helper only ever
  called under a lock is analyzed as holding it.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Set, Tuple

from deepspeed_tpu.tools.jaxlint.core import _iter_stmts, call_name, unparse
from deepspeed_tpu.tools.threadlint.cfg import CFG, build_cfg

__all__ = ["Program", "FunctionInfo", "ClassInfo", "static_lock_graph"]

#: factory call suffixes -> lock kind (resolution is suffix-based so both
#: ``make_lock`` and ``threads.make_lock`` and the fully resolved dotted
#: path match)
_FACTORIES = {"make_lock": "lock", "make_rlock": "rlock",
              "make_semaphore": "semaphore", "make_condition": "condition"}
_RAW_CTORS = {"threading.Lock": "lock", "threading.RLock": "rlock",
              "threading.Semaphore": "semaphore",
              "threading.BoundedSemaphore": "semaphore",
              "threading.Condition": "condition"}
_EXECUTOR_CTORS = ("concurrent.futures.ThreadPoolExecutor",
                   "concurrent.futures.thread.ThreadPoolExecutor",
                   "ThreadPoolExecutor")
_ORDERED_KINDS = ("lock", "rlock")   # semaphores/conditions don't order

MAIN_ROLE = "main"


@dataclass
class CallSite:
    dotted: str                  # resolved dotted call text
    node: ast.Call
    held: Tuple[str, ...]        # lexical with-stack of lock names
    target: Optional["FunctionInfo"] = None


@dataclass
class AttrWrite:
    attr: str
    node: ast.stmt
    held: Tuple[str, ...]


@dataclass
class WithRegion:
    lock: str
    kind: str
    node: ast.stmt
    held: Tuple[str, ...]        # locks already held when this one is taken


@dataclass
class AcquireCall:
    lock: Optional[str]          # resolved name (None = unknown receiver)
    kind: str
    receiver: str                # unparse of the receiver expression
    node: ast.stmt               # the enclosing statement
    in_test: bool                # ``if x.acquire(False):`` style


class FunctionInfo:
    def __init__(self, qualname: str, module, node: ast.AST,
                 cls: Optional["ClassInfo"], name: str):
        self.qualname = qualname
        self.module = module
        self.node = node
        self.cls = cls
        self.name = name
        self.declared_role: Optional[str] = None
        self.role_seeds: Set[str] = set()
        self.roles: Set[str] = set()
        self.calls: List[CallSite] = []
        self.with_regions: List[WithRegion] = []
        self.acquire_calls: List[AcquireCall] = []
        self.attr_writes: List[AttrWrite] = []
        self.local_locks: Dict[str, Tuple[str, str]] = {}  # var -> (name, kind)
        self.callers: Set[str] = set()
        self.always_held: Set[str] = set()
        self._cfg: Optional[CFG] = None

    @property
    def path(self) -> str:
        return self.module.path

    @property
    def cfg(self) -> CFG:
        if self._cfg is None:
            self._cfg = build_cfg(self.node)
        return self._cfg

    def effective_roles(self) -> Set[str]:
        return self.roles if self.roles else {MAIN_ROLE}

    def __repr__(self) -> str:
        return f"FunctionInfo({self.qualname})"


class ClassInfo:
    def __init__(self, name: str, module, node: ast.ClassDef):
        self.name = name
        self.module = module
        self.node = node
        self.methods: Dict[str, FunctionInfo] = {}
        self.lock_attrs: Dict[str, Tuple[str, str]] = {}  # attr -> (name, kind)
        self.guards: Dict[str, str] = {}    # attr -> lock name | "none"
        self.attr_types: Dict[str, str] = {}  # attr -> class name
        self.exec_attrs: Dict[str, Optional[str]] = {}  # attr -> role
        self.thread_attrs: Dict[str, ast.stmt] = {}
        self.executor_sites: List[Tuple[str, ast.stmt, FunctionInfo]] = []
        self.thread_sites: List[Tuple[str, ast.stmt, FunctionInfo]] = []

    def __repr__(self) -> str:
        return f"ClassInfo({self.name})"


def _literal_str(node: Optional[ast.AST]) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _kw(call: ast.Call, name: str) -> Optional[ast.AST]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _factory_in(expr: ast.AST) -> Optional[Tuple[str, str]]:
    """Find a ``make_lock("name")``-style factory call anywhere inside
    ``expr`` (handles ``setdefault(key, make_lock(...))``). Returns
    ``(name, kind)`` when exactly one unambiguous factory call is found."""
    found: List[Tuple[str, str]] = []
    for node in ast.walk(expr):
        if not isinstance(node, ast.Call):
            continue
        tail = call_name(node).rsplit(".", 1)[-1]
        kind = _FACTORIES.get(tail)
        if kind and node.args:
            name = _literal_str(node.args[0])
            if name:
                found.append((name, kind))
    return found[0] if len(found) == 1 else None


class Program:
    def __init__(self):
        self.modules: Dict[str, object] = {}
        self.classes: Dict[str, ClassInfo] = {}          # class name -> info
        self.functions: Dict[str, FunctionInfo] = {}     # qualname -> info
        #: attr name -> lock (name, kind) when unambiguous program-wide
        #: (resolves ``req._emit_lock`` without knowing ``req``'s type)
        self.attr_locks: Dict[str, Optional[Tuple[str, str]]] = {}
        #: module dotted name -> {func name -> FunctionInfo}
        self.mod_funcs: Dict[str, Dict[str, FunctionInfo]] = {}
        self._mod_funcs_cache: Dict[str, Optional[Dict[str, FunctionInfo]]] = {}
        self.config = None

    # ------------------------------------------------------------------ #
    # construction
    # ------------------------------------------------------------------ #

    @classmethod
    def build(cls, modules: Dict[str, object], config=None) -> "Program":
        prog = cls()
        prog.config = config
        prog.modules = modules
        for path, mod in modules.items():
            prog._register_module(mod)
        for path, mod in modules.items():
            prog._scan_creations(mod)
        for fn in list(prog.functions.values()):
            prog._scan_function(fn)
        prog._resolve_calls()
        prog._seed_and_propagate_roles()
        prog._compute_always_held()
        return prog

    @staticmethod
    def _dotted_module(path: str) -> str:
        p = path.replace("\\", "/")
        if p.endswith(".py"):
            p = p[:-3]
        return p.strip("/").replace("/", ".")

    def _register_module(self, mod) -> None:
        dotted = self._dotted_module(mod.path)
        funcs = self.mod_funcs.setdefault(dotted, {})

        def register_fn(node, cls_info, parent_qual):
            qual = f"{parent_qual}.{node.name}" if parent_qual else node.name
            qualname = f"{mod.path}::{qual}"
            fi = FunctionInfo(qualname, mod, node, cls_info, node.name)
            fi.declared_role = self._declared_role(mod, node)
            self.functions[qualname] = fi
            if cls_info is not None and parent_qual == cls_info.name:
                cls_info.methods[node.name] = fi
            elif cls_info is None and parent_qual == "":
                funcs[node.name] = fi
            for child in node.body:
                walk(child, cls_info, qual)
            return fi

        def walk(node, cls_info, parent_qual):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                register_fn(node, cls_info, parent_qual)
            elif isinstance(node, ast.ClassDef) and parent_qual == "":
                ci = self.classes.setdefault(node.name,
                                             ClassInfo(node.name, mod, node))
                for child in node.body:
                    walk(child, ci, node.name)
            elif isinstance(node, (ast.If, ast.Try)):
                for child in ast.iter_child_nodes(node):
                    if isinstance(child, ast.stmt):
                        walk(child, cls_info, parent_qual)

        for node in mod.tree.body:
            walk(node, None, "")

    def _declared_role(self, mod, node) -> Optional[str]:
        for deco in getattr(node, "decorator_list", ()):
            if isinstance(deco, ast.Call):
                if call_name(deco).rsplit(".", 1)[-1] == "thread_role" \
                        and deco.args:
                    name = _literal_str(deco.args[0])
                    if name:
                        return name
        return mod.role_annotations.get(node.lineno)

    # -- creation sites (locks, executors, threads, attr types) --------- #

    def _scan_creations(self, mod) -> None:
        for ci in [c for c in self.classes.values() if c.module is mod]:
            for meth in ci.methods.values():
                self._scan_method_creations(ci, meth)
        # register guard annotations found on any annotated self-assign
        # (already handled inside _scan_method_creations)

    def _scan_method_creations(self, ci: ClassInfo, fn: FunctionInfo) -> None:
        mod = fn.module
        # assignments are statements: skip descending into expressions
        for stmt in _iter_stmts(fn.node):
            if not isinstance(stmt, (ast.Assign, ast.AnnAssign)):
                continue
            targets = stmt.targets if isinstance(stmt, ast.Assign) \
                else [stmt.target]
            value = stmt.value
            if value is None:
                continue
            for tgt in targets:
                if not (isinstance(tgt, ast.Attribute)
                        and isinstance(tgt.value, ast.Name)
                        and tgt.value.id == "self"):
                    continue
                attr = tgt.attr
                guard = mod.guard_annotations.get(stmt.lineno)
                if guard is not None:
                    ci.guards.setdefault(attr, guard)
                resolved = self._creation_of(mod, value)
                if resolved is None:
                    continue
                kind, payload = resolved
                if kind == "lock":
                    name, lkind = payload
                    if name is None:
                        name = f"{ci.name}.{attr}"
                    ci.lock_attrs.setdefault(attr, (name, lkind))
                    prior = self.attr_locks.get(attr, ())
                    if prior == ():
                        self.attr_locks[attr] = (name, lkind)
                    elif prior is not None and prior[0] != name:
                        self.attr_locks[attr] = None   # ambiguous
                elif kind == "executor":
                    role = mod.role_annotations.get(stmt.lineno) or payload
                    ci.exec_attrs.setdefault(attr, role)
                elif kind == "thread":
                    ci.thread_attrs.setdefault(attr, stmt)
                elif kind == "class":
                    ci.attr_types.setdefault(attr, payload)

    def _creation_of(self, mod, value: ast.AST):
        """Classify ``self.x = <value>`` creation sites."""
        if not isinstance(value, ast.Call):
            fac = _factory_in(value)
            return ("lock", fac) if fac else None
        dotted = mod.resolve(call_name(value))
        tail = dotted.rsplit(".", 1)[-1]
        if tail in _FACTORIES:
            name = _literal_str(value.args[0]) if value.args else None
            return ("lock", (name, _FACTORIES[tail]))
        if dotted in _RAW_CTORS:
            return ("lock", (None, _RAW_CTORS[dotted]))
        if dotted in _EXECUTOR_CTORS or tail == "ThreadPoolExecutor":
            prefix = _literal_str(_kw(value, "thread_name_prefix"))
            return ("executor", prefix)
        if dotted == "threading.Thread":
            return ("thread", None)
        if tail in self.classes:
            return ("class", tail)
        fac = _factory_in(value)
        return ("lock", fac) if fac else None

    # -- per-function facts ---------------------------------------------- #

    def resolve_lock_expr(self, fn: FunctionInfo, expr: ast.AST) \
            -> Optional[Tuple[str, str]]:
        """Resolve a lock-valued expression to ``(name, kind)``."""
        if isinstance(expr, ast.Attribute):
            if isinstance(expr.value, ast.Name) and expr.value.id == "self" \
                    and fn.cls is not None:
                hit = fn.cls.lock_attrs.get(expr.attr)
                if hit:
                    return hit
            hit = self.attr_locks.get(expr.attr)
            if hit:
                return hit
            return None
        if isinstance(expr, ast.Name):
            return fn.local_locks.get(expr.id)
        if isinstance(expr, ast.Call):
            fac = _factory_in(expr)
            return fac
        return None

    def _scan_function(self, fn: FunctionInfo) -> None:
        # locals bound to named locks (incl. through .setdefault(...))
        for stmt in self._scope_stmts(fn.node):
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name):
                var = stmt.targets[0].id
                fac = _factory_in(stmt.value)
                if fac:
                    fn.local_locks[var] = fac
                elif isinstance(stmt.value, ast.Call):
                    # raw local Condition() — TL006 needs the kind; raw
                    # local Lock()s stay anonymous on purpose (they can't
                    # participate in cross-function ordering)
                    dotted = fn.module.resolve(call_name(stmt.value))
                    if _RAW_CTORS.get(dotted) == "condition":
                        fn.local_locks[var] = (f"<local:{var}>", "condition")

        self._walk_scope(fn, fn.node.body, held=())

    def _scope_stmts(self, root) -> Iterable[ast.stmt]:
        """Statements of this function's own scope (no nested defs)."""
        out: List[ast.stmt] = []

        def rec(body):
            for stmt in body:
                if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.ClassDef)):
                    continue
                out.append(stmt)
                for name in ("body", "orelse", "finalbody"):
                    rec(getattr(stmt, name, []) or [])
                for h in getattr(stmt, "handlers", []) or []:
                    rec(h.body)

        rec(root.body)
        return out

    def _walk_scope(self, fn: FunctionInfo, body: List[ast.stmt],
                    held: Tuple[str, ...]) -> None:
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue

            if isinstance(stmt, (ast.With, ast.AsyncWith)):
                inner = held
                for item in stmt.items:
                    hit = self.resolve_lock_expr(fn, item.context_expr)
                    self._scan_exprs(fn, [item.context_expr], inner, stmt)
                    if hit:
                        name, kind = hit
                        fn.with_regions.append(
                            WithRegion(name, kind, stmt, inner))
                        if kind in _ORDERED_KINDS:
                            inner = inner + (name,)
                self._walk_scope(fn, stmt.body, inner)
                continue

            # expressions of THIS statement (head only — children bodies
            # recurse below with their own held context)
            self._scan_exprs(fn, self._head_exprs(stmt), held, stmt)

            # bare acquire() statements (TL004)
            self._scan_acquire(fn, stmt, held)

            for name in ("body", "orelse", "finalbody"):
                sub = getattr(stmt, name, None)
                if sub:
                    self._walk_scope(fn, sub, held)
            for h in getattr(stmt, "handlers", []) or []:
                self._walk_scope(fn, h.body, held)

            # attribute writes
            if isinstance(stmt, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                targets = stmt.targets if isinstance(stmt, ast.Assign) \
                    else [stmt.target]
                for tgt in targets:
                    if isinstance(tgt, ast.Attribute) \
                            and isinstance(tgt.value, ast.Name) \
                            and tgt.value.id == "self":
                        fn.attr_writes.append(
                            AttrWrite(tgt.attr, stmt, held))

    @staticmethod
    def _head_exprs(stmt: ast.stmt) -> List[ast.AST]:
        """The expressions evaluated AT this statement (not in child suites)."""
        out: List[ast.AST] = []
        for name, value in ast.iter_fields(stmt):
            if name in ("body", "orelse", "finalbody", "handlers"):
                continue
            if isinstance(value, ast.AST):
                out.append(value)
            elif isinstance(value, list):
                out.extend(v for v in value if isinstance(v, ast.expr))
        return out

    def _scan_exprs(self, fn: FunctionInfo, exprs: List[ast.AST],
                    held: Tuple[str, ...], stmt: ast.stmt) -> None:
        for expr in exprs:
            for node in ast.walk(expr):
                if isinstance(node, (ast.Lambda,)):
                    continue
                if isinstance(node, ast.Call):
                    dotted = fn.module.resolve(call_name(node))
                    if dotted:
                        fn.calls.append(CallSite(dotted, node, held))

    def _scan_acquire(self, fn: FunctionInfo, stmt: ast.stmt,
                      held: Tuple[str, ...]) -> None:
        call = None
        in_test = False
        if isinstance(stmt, ast.Expr) and isinstance(stmt.value, ast.Call):
            call = stmt.value
        elif isinstance(stmt, (ast.If, ast.While)) \
                and isinstance(stmt.test, ast.Call):
            call = stmt.test
            in_test = True
        elif isinstance(stmt, ast.If) and isinstance(stmt.test, ast.UnaryOp) \
                and isinstance(stmt.test.operand, ast.Call):
            call = stmt.test.operand
            in_test = True
        if call is None or not isinstance(call.func, ast.Attribute) \
                or call.func.attr != "acquire":
            return
        recv = call.func.value
        hit = self.resolve_lock_expr(fn, recv)
        name, kind = hit if hit else (None, "lock")
        fn.acquire_calls.append(
            AcquireCall(name, kind, unparse(recv), stmt, in_test))

    # -- call graph ------------------------------------------------------ #

    def _resolve_calls(self) -> None:
        for fn in self.functions.values():
            for site in fn.calls:
                site.target = self._resolve_target(fn, site)
                if site.target is not None:
                    site.target.callers.add(fn.qualname)

    def _resolve_target(self, fn: FunctionInfo, site: CallSite) \
            -> Optional[FunctionInfo]:
        func = site.node.func
        # self.m(...)
        if isinstance(func, ast.Attribute) and fn.cls is not None \
                and isinstance(func.value, ast.Name) \
                and func.value.id == "self":
            return fn.cls.methods.get(func.attr)
        # self.attr.m(...)
        if isinstance(func, ast.Attribute) \
                and isinstance(func.value, ast.Attribute) \
                and isinstance(func.value.value, ast.Name) \
                and func.value.value.id == "self" and fn.cls is not None:
            tname = fn.cls.attr_types.get(func.value.attr)
            if tname and tname in self.classes:
                return self.classes[tname].methods.get(func.attr)
        # bare name: nested def in the same function, else module function
        if isinstance(func, ast.Name):
            nested = self.functions.get(
                f"{fn.qualname}.{func.id}")
            if nested is not None:
                return nested
            dotted_mod = self._dotted_module(fn.module.path)
            local = self.mod_funcs.get(dotted_mod, {}).get(func.id)
            if local is not None:
                return local
        # imported module function: alias.m() resolved through imports
        dotted = site.dotted
        if "." in dotted:
            mod_part, _, fname = dotted.rpartition(".")
            funcs = self._funcs_for_module(mod_part)
            if funcs:
                return funcs.get(fname)
        return None

    def _funcs_for_module(self, mod_part: str) \
            -> Optional[Dict[str, FunctionInfo]]:
        """Module-function table for an import-resolved dotted module; falls
        back to a unique suffix match (the linted tree may be rooted below
        where imports are absolute from)."""
        funcs = self.mod_funcs.get(mod_part)
        if funcs is not None:
            return funcs
        cached = self._mod_funcs_cache.get(mod_part, False)
        if cached is not False:
            return cached
        hits = [v for k, v in self.mod_funcs.items()
                if k.endswith("." + mod_part) or mod_part.endswith("." + k)]
        out = hits[0] if len(hits) == 1 else None
        self._mod_funcs_cache[mod_part] = out
        return out

    # -- roles ----------------------------------------------------------- #

    def _seed_and_propagate_roles(self) -> None:
        for fn in self.functions.values():
            if fn.declared_role:
                fn.role_seeds.add(fn.declared_role)

        # Thread(target=...) and executor submits
        for fn in self.functions.values():
            for site in fn.calls:
                node = site.node
                tail = site.dotted.rsplit(".", 1)[-1]
                if site.dotted == "threading.Thread" or tail == "Thread":
                    target = _kw(node, "target")
                    if target is None:
                        continue
                    tfn = self._resolve_value_function(fn, target)
                    if tfn is None:
                        continue
                    if not tfn.declared_role:
                        name = _literal_str(_kw(node, "name")) \
                            or fn.module.role_annotations.get(node.lineno)
                        tfn.role_seeds.add(name or f"thread:{tfn.name}")
                elif tail == "submit" and isinstance(node.func, ast.Attribute):
                    recv = node.func.value
                    role = fn.module.role_annotations.get(node.lineno)
                    if role is None and isinstance(recv, ast.Attribute) \
                            and isinstance(recv.value, ast.Name) \
                            and recv.value.id == "self" and fn.cls is not None:
                        role = fn.cls.exec_attrs.get(recv.attr)
                    if role is None:
                        continue
                    if node.args:
                        tfn = self._resolve_value_function(fn, node.args[0])
                        if tfn is not None and not tfn.declared_role:
                            tfn.role_seeds.add(role)

        for fn in self.functions.values():
            fn.roles = set(fn.role_seeds)
        fixed = {fn.qualname for fn in self.functions.values()
                 if fn.role_seeds}
        for fn in self.functions.values():
            if fn.qualname not in fixed and not fn.callers:
                fn.roles.add(MAIN_ROLE)

        changed = True
        while changed:
            changed = False
            for fn in self.functions.values():
                for site in fn.calls:
                    tgt = site.target
                    if tgt is None or tgt.qualname in fixed:
                        continue
                    add = fn.roles - tgt.roles
                    if add:
                        tgt.roles |= add
                        changed = True

    def _resolve_value_function(self, fn: FunctionInfo, expr: ast.AST) \
            -> Optional[FunctionInfo]:
        """Resolve ``target=self._run`` / ``target=runner`` references."""
        if isinstance(expr, ast.Attribute) \
                and isinstance(expr.value, ast.Name) \
                and expr.value.id == "self" and fn.cls is not None:
            return fn.cls.methods.get(expr.attr)
        if isinstance(expr, ast.Name):
            nested = self.functions.get(f"{fn.qualname}.{expr.id}")
            if nested is not None:
                return nested
            dotted_mod = self._dotted_module(fn.module.path)
            return self.mod_funcs.get(dotted_mod, {}).get(expr.id)
        return None

    # -- interprocedural held locks -------------------------------------- #

    def _compute_always_held(self) -> None:
        # optimistic init: every non-root function "holds everything";
        # intersection over call sites then shrinks to what is guaranteed
        universe = object()
        state: Dict[str, object] = {}
        for fn in self.functions.values():
            state[fn.qualname] = set() if not fn.callers else universe

        changed = True
        rounds = 0
        while changed and rounds < 50:
            changed = False
            rounds += 1
            for fn in self.functions.values():
                for site in fn.calls:
                    tgt = site.target
                    if tgt is None:
                        continue
                    mine = state[fn.qualname]
                    mine = set() if mine is universe else mine
                    incoming = set(site.held) | mine
                    cur = state[tgt.qualname]
                    new = incoming if cur is universe \
                        else (cur & incoming)
                    if new != cur:
                        state[tgt.qualname] = new
                        changed = True
        for fn in self.functions.values():
            held = state[fn.qualname]
            fn.always_held = set() if held is universe else set(held)

    # ------------------------------------------------------------------ #
    # derived facts for rules
    # ------------------------------------------------------------------ #

    def transitive_acquires(self, fn: FunctionInfo,
                            _memo: Optional[Dict[str, Set[str]]] = None,
                            _stack: Optional[Set[str]] = None) -> Set[str]:
        """Ordered-lock names ``fn`` may acquire, directly or through the
        call graph."""
        memo = _memo if _memo is not None else {}
        stack = _stack if _stack is not None else set()
        if fn.qualname in memo:
            return memo[fn.qualname]
        if fn.qualname in stack:
            return set()
        stack.add(fn.qualname)
        out: Set[str] = {r.lock for r in fn.with_regions
                         if r.kind in _ORDERED_KINDS}
        out |= {a.lock for a in fn.acquire_calls
                if a.lock and a.kind in _ORDERED_KINDS}
        for site in fn.calls:
            if site.target is not None:
                out |= self.transitive_acquires(site.target, memo, stack)
        stack.discard(fn.qualname)
        memo[fn.qualname] = out
        return out

    def lock_edges(self) -> Dict[Tuple[str, str], Tuple[str, int]]:
        """The static acquisition graph: ``(held, acquired) -> (path,
        line)`` of one witness site. Includes call-graph-transitive
        acquisitions under a held lock."""
        memo: Dict[str, Set[str]] = {}
        edges: Dict[Tuple[str, str], Tuple[str, int]] = {}
        for fn in self.functions.values():
            base = tuple(sorted(fn.always_held))
            for region in fn.with_regions:
                if region.kind not in _ORDERED_KINDS:
                    continue
                for h in set(region.held) | set(base):
                    if h != region.lock:
                        edges.setdefault((h, region.lock),
                                         (fn.path, region.node.lineno))
            for site in fn.calls:
                held = set(site.held) | set(base)
                if not held or site.target is None:
                    continue
                for inner in self.transitive_acquires(site.target, memo):
                    for h in held:
                        if h != inner:
                            edges.setdefault((h, inner),
                                             (fn.path, site.node.lineno))
        return edges


def static_lock_graph(paths: Iterable[str], config=None) \
        -> Set[Tuple[str, str]]:
    """The static lock-acquisition edge set for the given tree — what the
    scenario tests compare locksan's observed edges against (static must be a
    superset)."""
    from deepspeed_tpu.tools.threadlint.config import (ThreadLintConfig,
                                                       find_config)
    from deepspeed_tpu.tools.threadlint.core import _parse_modules
    from deepspeed_tpu.tools.jaxlint.core import iter_files
    if config is None:
        found = find_config(next(iter(paths)))
        config = ThreadLintConfig.load(found) if found else ThreadLintConfig()
    files = iter_files(paths, exclude=config.exclude)
    modules, _errors = _parse_modules(files, in_memory=False)
    return set(Program.build(modules, config).lock_edges())
