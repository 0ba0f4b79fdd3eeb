"""From the stamps on a serving request to what its user felt.

A handle of ``ServingFrontend`` carries ``arrival_t`` (``perf_counter`` at
``submit``), ``admit_t``, ``ttft_ms`` (first token after ``arrival_t``) and
``tbt_ms`` (the gap before each later token, 0.0 for a token that arrived
in the same drain as the one before). Latency here is counted from when the
request was DUE, not from ``submit``: a generator that ran late then shows
as a longer wait, not a shorter one.
"""

from __future__ import annotations

from itertools import accumulate
from typing import Any, List, Optional, Sequence


def lateness_ms(sent) -> float:
    """How long after its due time the request reached the server."""
    return 1e3 * (sent.handle.arrival_t - sent.due_t)


def ttft_ms(sent) -> Optional[float]:
    """Due time to first token; ``None`` if no token came."""
    if sent.handle.ttft_ms is None:
        return None
    return lateness_ms(sent) + sent.handle.ttft_ms


def queue_wait_ms(sent) -> Optional[float]:
    """Due time to admission by the frontend."""
    if sent.handle.admit_t is None:
        return None
    return 1e3 * (sent.handle.admit_t - sent.due_t)


def token_times(handle: Any) -> List[float]:
    """``perf_counter`` time at which each token reached the client."""
    if handle.ttft_ms is None:
        return []
    gaps = [handle.ttft_ms] + list(handle.tbt_ms)
    return [handle.arrival_t + 1e-3 * ms for ms in accumulate(gaps)]


def tokens_between(sents: Sequence, t0: float, t1: float) -> int:
    """Tokens that arrived in ``[t0, t1)``, over all requests."""
    return sum(1 for s in sents for t in token_times(s.handle)
               if t0 <= t < t1)


def complete(sent, vocab: int) -> bool:
    """Finished with exactly the tokens asked for, all inside the
    vocabulary."""
    h = sent.handle
    return (h.status == "finished"
            and len(h.tokens) == sent.request.max_new_tokens
            and all(0 <= t < vocab for t in h.tokens))
