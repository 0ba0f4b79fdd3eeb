"""Deterministic fault injection for the preemption-tolerance subsystem.

Robustness code is only as real as the failures it has survived. This module
is the single switchboard through which the checkpoint writers
(``checkpoint/engine.py``), the NVMe AIO paths
(``ops/native/aio.py`` / ``runtime/swap_tensor/``), the training step loop
(``runtime/engine.py``), and the elastic agent are made to fail ON DEMAND —
deterministically, so a failing run replays bit-for-bit:

- every **site** (a string like ``"ckpt.writer"``) keeps its own hit counter;
- a :class:`FaultSpec` fires at an exact hit index (``at``), on a cadence
  (``every``), or with a seeded per-hit probability (``p`` — keyed by
  ``(seed, site, hit)``, so the same plan + seed always fails the same hits);
- the **action** is one of ``raise`` (a :class:`InjectedFault`), ``errno``
  (sites that speak the AIO return-code contract get a negative errno
  instead of an exception), ``stall`` (sleep ``delay_s`` then proceed — the
  slow-writer / slow-disk case), or ``kill`` (``os._exit(KILL_EXIT_CODE)``,
  the SIGTERM-style mid-step death a preempted worker suffers; usable from
  any thread, including a checkpoint writer thread mid-write).

Nothing is installed by default and ``maybe_fail`` is a two-instruction
no-op when inactive, so production hot paths pay nothing. A subprocess is
given its plan through the ``DSTPU_FAULTS`` env var (see :func:`parse_plan`
for the grammar; ``tests/unit/test_rolling_checkpoint.py`` kills and resumes
a run that way), e.g.::

    DSTPU_FAULTS="step.kill:at=8:action=kill"
    DSTPU_FAULTS="ckpt.writer:at=3:action=kill;aio.read:every=5:action=errno:errno=5"

Known sites (grep for ``maybe_fail``/``maybe_rc`` to audit):

==================  =========================================================
``step.kill``       top of ``engine.train_batch`` (mid-run preemption)
``ckpt.writer``     inside ``_atomic_savez`` before the write (writer crash)
``ckpt.stall``      inside ``_atomic_savez`` (slow writer; pair with
                    ``action=stall``)
``aio.read``        ``AsyncIOHandle`` read submit (rc contract)
``aio.write``       ``AsyncIOHandle`` write submit (rc contract)
``aio.wait``        ``AsyncIOHandle.wait`` completion (rc contract; the
                    real wait still runs first so buffers stay coherent)
``agent.run``       ``DSElasticAgent`` before each (re)start attempt
==================  =========================================================

Serving-side sites (ISSUE 12 — the chaos surface
``tests/unit/test_serving_health.py`` replays against; docs/SERVING.md
"Failure semantics"):

========================  ===================================================
``serve.engine_step``     top of ``ServingFrontend.step()`` — ``raise``
                          crashes the replica's serving loop, ``stall``
                          wedges it (the health monitor's stall-deadline
                          case). Replica-scoped form
                          ``serve.engine_step.<replica>`` (the label a
                          ``ServingCluster`` assigns) targets ONE replica
                          deterministically.
``serve.prefill_worker``  ``PrefillWorker`` batch loop (disaggregated
                          prefill) — also replica-scoped
                          (``serve.prefill_worker.<replica>``).
``serve.handoff``         inside each deadline-wrapped prefill->decode
                          handoff attempt (``raise`` exhausts the
                          ``retry_call`` budget; ``stall`` past
                          ``handoff_timeout_s`` surfaces ``IOTimeout``).
``serve.kv_fetch``        ``engine.fetch_pages`` (page-fabric gather:
                          preempt-offload, export_kv).
``serve.kv_put``          ``engine.put_pages`` (page-fabric scatter:
                          restore, import_kv).
``serve.lora_fault``      ``LoraAdapterRegistry._ensure_resident`` — inside
                          an adapter fault-in, after pages are allocated
                          but before the scatter lands (cancel-while-
                          faulting must roll refcounts, bindings and free
                          pages back to baseline).
========================  ===================================================
"""

from __future__ import annotations

import errno as _errno
import os
import random
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from deepspeed_tpu.utils.logging import logger
from deepspeed_tpu.utils.threads import make_lock

#: exit status of an injected ``action=kill`` — distinguishable from a crash
KILL_EXIT_CODE = 17

_ENV_VAR = "DSTPU_FAULTS"


class InjectedFault(OSError):
    """The exception an ``action=raise`` site surfaces. Subclasses OSError so
    IO-shaped retry policies (``retry_on=(OSError,)``) treat injected and
    real IO failures identically."""


@dataclass
class FaultSpec:
    """When and how one site fails. ``at`` is 1-based (the Nth hit); ``every``
    fires on hits that are multiples of it; ``p`` is a seeded per-hit
    probability. Multiple triggers OR together. ``max_fires`` bounds the
    total number of firings (0 = unbounded)."""

    site: str
    at: int = 0
    every: int = 0
    p: float = 0.0
    action: str = "raise"          # raise | errno | stall | kill
    errno: int = _errno.EIO
    delay_s: float = 0.2
    max_fires: int = 0
    fires: int = 0

    def should_fire(self, hit: int, seed: int) -> bool:
        if self.max_fires and self.fires >= self.max_fires:
            return False
        if self.at and hit == self.at:
            return True
        if self.every and hit % self.every == 0:
            return True
        if self.p > 0.0:
            # keyed, not sequential: the decision for (site, hit) never
            # depends on how many other sites drew before it
            return random.Random(f"{seed}:{self.site}:{hit}").random() < self.p
        return False


class FaultInjector:
    """Holds the active plan and the per-site hit counters (thread-safe:
    writer pools and the step loop hit sites concurrently)."""

    def __init__(self, specs: List[FaultSpec], seed: int = 0):
        self.seed = int(seed)
        self._specs: Dict[str, List[FaultSpec]] = {}
        for s in specs:
            self._specs.setdefault(s.site, []).append(s)
        self._hits: Dict[str, int] = {}
        self._lock = make_lock("utils.fault.hits")
        #: (site, hit, action) tuples of every firing, for assertions
        self.fired: List[tuple] = []

    def hit(self, site: str) -> Optional[FaultSpec]:
        """Count a hit at ``site``; return the spec to execute, if any."""
        with self._lock:
            n = self._hits.get(site, 0) + 1
            self._hits[site] = n
            for spec in self._specs.get(site, ()):
                if spec.should_fire(n, self.seed):
                    spec.fires += 1
                    self.fired.append((site, n, spec.action))
                    return spec
        return None

    def hits(self, site: str) -> int:
        with self._lock:
            return self._hits.get(site, 0)


_active: Optional[FaultInjector] = None


def install(injector: Optional[FaultInjector]) -> Optional[FaultInjector]:
    """Install (or clear, with None) the process-wide injector."""
    global _active
    _active = injector
    return injector


def active() -> Optional[FaultInjector]:
    return _active


def clear() -> None:
    install(None)


def parse_plan(plan: str, seed: int = 0) -> FaultInjector:
    """``site:key=val:key=val;site2:...`` -> injector. Keys: at, every, p,
    action, errno, delay_s, max_fires."""
    specs = []
    for part in plan.split(";"):
        part = part.strip()
        if not part:
            continue
        fields = part.split(":")
        spec = FaultSpec(site=fields[0])
        for kv in fields[1:]:
            key, _, val = kv.partition("=")
            key = key.strip()
            if key == "action":
                spec.action = val.strip()
            elif key in ("at", "every", "errno", "max_fires"):
                setattr(spec, key, int(val))
            elif key in ("p", "delay_s"):
                setattr(spec, key, float(val))
            else:
                raise ValueError(f"unknown fault-spec key '{key}' in {part!r}")
        if spec.action not in ("raise", "errno", "stall", "kill"):
            raise ValueError(f"unknown fault action '{spec.action}'")
        specs.append(spec)
    return FaultInjector(specs, seed=seed)


def install_from_env() -> Optional[FaultInjector]:
    """Install a plan from ``DSTPU_FAULTS`` (no-op when unset). Called by
    ``deepspeed_tpu.initialize`` so subprocess workers arm faults without
    touching user code; idempotent — an already-installed injector wins."""
    if _active is not None:
        return _active
    plan = os.environ.get(_ENV_VAR, "").strip()
    if not plan:
        return None
    seed = int(os.environ.get("DSTPU_SEED", "0") or 0)
    inj = install(parse_plan(plan, seed=seed))
    logger.warning(f"fault injection ARMED from ${_ENV_VAR}: {plan!r}")
    return inj


def _flight_record(site: str, action: str) -> None:
    """Dump the span tracer's flight recorder before a fault surfaces.

    ``action=kill`` dies via ``os._exit`` — no atexit, no finally — so the
    ONLY postmortem timeline a preempted run can leave is written here,
    first. ``action=raise`` dumps too: an InjectedFault may unwind through
    teardown paths that never reach a clean export. No-op (and never
    raising) when tracing is off — the kill must stay a kill."""
    try:
        from deepspeed_tpu.monitor.trace import tracer
        tracer.crash_dump(f"injected {action} at {site}")
    except Exception:   # pragma: no cover - the fault must still fire
        pass


def _execute(spec: FaultSpec, site: str):
    if spec.action == "stall":
        logger.warning(f"fault injection: stalling {spec.delay_s}s at {site}")
        time.sleep(spec.delay_s)
        return None
    if spec.action == "kill":
        logger.warning(f"fault injection: killing process at {site}")
        _flight_record(site, "kill")
        # SIGTERM-style: no atexit, no finally blocks — the preempted-VM model
        os._exit(KILL_EXIT_CODE)
    if spec.action == "errno":
        return -abs(spec.errno)
    _flight_record(site, "raise")
    raise InjectedFault(spec.errno, f"injected fault at {site}")


def maybe_fail(site: str) -> None:
    """Exception-contract sites: raises :class:`InjectedFault` / stalls /
    kills when the active plan says so; free when no injector is installed."""
    if _active is None:
        return
    spec = _active.hit(site)
    if spec is None:
        return
    rc = _execute(spec, site)
    if rc is not None:  # an errno spec on an exception-contract site
        raise InjectedFault(-rc, f"injected fault at {site}")


def maybe_rc(site: str) -> int:
    """Return-code-contract sites (the AIO surface): returns a negative errno
    when firing with ``action=errno``; stalls return 0 after sleeping; raise/
    kill behave as in :func:`maybe_fail`."""
    if _active is None:
        return 0
    spec = _active.hit(site)
    if spec is None:
        return 0
    rc = _execute(spec, site)
    return rc if rc is not None else 0
