"""Sending generated requests to a server: an open loop that keeps to its
schedule whatever the server does, and a closed loop of clients that each
wait for their reply.

The server is anything with ``submit(prompt, max_new_tokens) -> handle``
where the handle has ``finished`` (bool). Every request sent is remembered
with the time it was DUE on the sender's clock (``time.perf_counter``, the
clock the serving frontend stamps its handles with), so that latency can be
counted from then (``chipbench/reduce/latency.py``).
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass
from typing import Any, Callable, List, Optional, Sequence, Tuple

from chipbench.traffic.generator import Request


@dataclass
class Sent:
    request: Request
    due_t: float          # perf_counter time at which it was due
    sent_t: float         # ... at which submit was called
    handle: Any


def _no_span(_name: str):
    return contextlib.nullcontext()


def replay_open(submit: Callable, requests: Sequence[Request], t0: float,
                marks: Sequence[Tuple[float, Callable[[], None]]] = (),
                each: Optional[Callable[[], None]] = None,
                span: Callable = _no_span,
                clock: Callable[[], float] = time.perf_counter,
                sleep: Callable[[float], None] = time.sleep) -> List[Sent]:
    """Send each request when it is due (``t0 + due_s``); one that is
    already late goes at once — the generator never waits for the server.
    ``marks`` are ``(seconds from t0, callback)``, called in order when
    their time has come, between sends; ``each`` is called after every
    send (to sample a gauge)."""
    sent: List[Sent] = []
    pending = sorted(marks, key=lambda m: m[0])

    def wait_until(t: float) -> None:
        delay = t - clock()
        if delay > 0:
            with span("waiting for arrival"):
                sleep(delay)

    def run_marks(up_to: float) -> None:
        while pending and pending[0][0] <= up_to:
            at, fn = pending.pop(0)
            wait_until(t0 + at)
            fn()

    for r in requests:
        run_marks(r.due_s)
        due_t = t0 + r.due_s
        wait_until(due_t)
        sent_t = clock()
        with span("harness submit"):
            handle = submit(r.prompt, r.max_new_tokens)
        sent.append(Sent(r, due_t, sent_t, handle))
        if each is not None:
            each()
    run_marks(float("inf"))
    return sent


def run_closed(submit: Callable, pool: Sequence[Request], clients: int,
               until: float,
               marks: Sequence[Tuple[float, Callable[[], None]]] = (),
               each: Optional[Callable[[], None]] = None,
               span: Callable = _no_span, poll_s: float = 0.002,
               clock: Callable[[], float] = time.perf_counter,
               sleep: Callable[[float], None] = time.sleep) -> List[Sent]:
    """``clients`` callers, each sending its next request (the pool's next,
    starting over at its end) when its last is finished, until ``until``
    (clock time); nothing is sent after it. A request is due when it is
    sent. ``marks`` are ``(clock time, callback)``."""
    sent: List[Sent] = []
    slots: List[Optional[Sent]] = [None] * clients
    pending = sorted(marks, key=lambda m: m[0])
    k = 0
    while True:
        now = clock()
        while pending and pending[0][0] <= now:
            pending.pop(0)[1]()
        if now >= until:
            break
        for i, s in enumerate(slots):
            if s is None or s.handle.finished:
                r = pool[k % len(pool)]
                k += 1
                t = clock()
                with span("harness submit"):
                    handle = submit(r.prompt, r.max_new_tokens)
                slots[i] = Sent(r, t, t, handle)
                sent.append(slots[i])
        if each is not None:
            each()
        with span("waiting for clients"):
            sleep(poll_s)
    while pending:
        pending.pop(0)[1]()
    return sent


def drain(sent: Sequence[Sent], timeout_s: float, poll_s: float = 0.01,
          clock: Callable[[], float] = time.perf_counter,
          sleep: Callable[[float], None] = time.sleep) -> bool:
    """Wait until every request sent is finished; False if ``timeout_s``
    passes first."""
    deadline = clock() + timeout_s
    while not all(s.handle.finished for s in sent):
        if clock() > deadline:
            return False
        sleep(poll_s)
    return True
