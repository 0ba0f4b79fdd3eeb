"""The one traffic generator: it reads a mix (a data file under
``chipbench/traffic/``) and a seed, and gives requests or batches.

A corrected copy of ``deepspeed_tpu/inference/v2/serving/loadgen.py`` in two
respects: a request carries the time at which it is DUE, so that whoever
replays it can count latency from then and report how late it was sent (the
original counts from ``submit``, so a late generator hides queueing); and
lengths come from a clipped distribution, not from a short list.

Every seed gives the same multiset of lengths and of gaps between arrivals,
in another order: the set is the distribution's quantiles at ``n`` evenly
spaced probabilities (its "stratified sample"), and the seed only permutes
it and draws the token ids. Two runs with different seeds then offer the
same work, so their difference is the system's and not the draw's.

The order is the seed's and still matters to a tail: a draw that puts long
prompts close together loads the replica more for a while. A mix judged by a
tail is run at a rate at which no order overloads the replica (the sweep,
``chipbench/sweep.py``, tries several orders at each rate).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


@dataclass
class Request:
    due_s: float              # seconds from the start of the schedule
    prompt: np.ndarray        # int32 token ids
    max_new_tokens: int
    measured: bool = True     # False for an open loop's ramp


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """A generator of its own for each use of the seed (any whole number)."""
    words = [int(seed) & 0xFFFFFFFF, int(seed) >> 32] + list(stream.encode())
    return np.random.default_rng(np.random.SeedSequence(words))


def _probabilities(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def length_set(dist: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` lengths: the quantiles of ``dist`` at evenly spaced
    probabilities, clipped to ``[min, max]``, sorted."""
    kind = dist["dist"]
    u = _probabilities(n)
    if kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo, hi = dist.get("min", 1), dist.get("max", None)
    x = np.clip(x, lo, hi)
    return np.round(x).astype(np.int64)


def gap_set(arrivals: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` gaps between arrivals in seconds, sorted, with mean exactly
    ``1 / rate_per_s``: the exponential distribution's quantiles
    (``poisson``, the one arrival process there is)."""
    rate = float(arrivals["rate_per_s"])
    if arrivals["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    x = -np.log1p(-_probabilities(n))
    return x * (n / rate / x.sum())


def _request_pool(mix: Dict[str, Any], n: int, seed: int, vocab: int,
                  stream: str = "") -> List[Request]:
    """``n`` requests without due times: lengths permuted by the seed,
    prompts and outputs permuted independently."""
    order = rng_for(seed, "requests" + stream)
    prompts = order.permutation(length_set(mix["prompt_tokens"], n))
    outputs = order.permutation(length_set(mix["output_tokens"], n))
    rng = rng_for(seed, "tokens" + stream)
    return [Request(0.0, rng.integers(0, vocab, size=int(p)).astype(np.int32),
                    int(m)) for p, m in zip(prompts, outputs)]


def _arrivals(mix: Dict[str, Any], start_s: float, seconds: float, seed: int,
              stream: str, vocab: int) -> List[Request]:
    """``rate x seconds`` requests over ``[start_s, start_s + seconds)``:
    the first is due at ``start_s``, each next one permuted gap later, and
    the last gap runs to the end."""
    n = round(float(mix["arrivals"]["rate_per_s"]) * seconds)
    if n < 1:
        return []
    gaps = rng_for(seed, "arrivals" + stream).permutation(
        gap_set(mix["arrivals"], n))
    due = start_s + np.cumsum(gaps) - gaps
    reqs = _request_pool(mix, n, seed, vocab, stream)
    for r, t in zip(reqs, due):
        r.due_s = float(t)
    return reqs


def open_schedule(mix: Dict[str, Any], seconds: float, seed: int,
                  vocab: int) -> List[Request]:
    """Arrivals of an open loop: ``ramp_s`` seconds of ramp (not measured),
    then the window. Each part is a stratified sample of its own, so that
    every seed puts the same requests and the same gaps INSIDE the window,
    in another order: were the two one sample, the seed would also choose
    which of the long prompts fall into the ramp."""
    ramp = float(mix.get("ramp_s", 0.0))
    warm = _arrivals(mix, 0.0, ramp, seed, "/ramp", vocab)
    for r in warm:
        r.measured = False
    return warm + _arrivals(mix, ramp, float(seconds), seed, "", vocab)


def closed_pool(mix: Dict[str, Any], seed: int, vocab: int) -> List[Request]:
    """The requests the clients of a closed loop take in turn
    (``pool_requests`` of them; the driver starts over if it runs out)."""
    return _request_pool(mix, int(mix["pool_requests"]), seed, vocab)


def train_batches(mix: Dict[str, Any], global_batch: int, seed: int,
                  vocab: int) -> List[Dict[str, np.ndarray]]:
    """``distinct_batches`` batches of uniform random token ids
    ``[global_batch, seq_len]``, labels the ids themselves."""
    rng = rng_for(seed, "batches")
    out = []
    for _ in range(int(mix["distinct_batches"])):
        ids = rng.integers(0, vocab, size=(global_batch, int(mix["seq_len"])),
                           dtype=np.int32)
        out.append({"input_ids": ids, "labels": ids})
    return out
