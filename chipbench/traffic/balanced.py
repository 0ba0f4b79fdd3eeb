"""A closed loop's pool in an order that gives every seed the same work in
every stretch of it.

``generator.closed_pool`` permutes the whole pool. That serves a cell whose
window takes requests that are all much alike; where prompts span 512 to
24,576 tokens and the window takes some 110 of the pool's 256, the seed then
also chooses WHICH of the long prompts fall into the window, and a single
one of them is 2% of it: the fault ``generator.open_schedule`` avoids by
sampling its ramp and its window apart. A closed loop has no such boundary
to sample on either side of (the clients decide when the window's first
request is sent), so here every stretch is a sample of its own:

- the lengths are the generator's: the same multisets of prompt and of
  output lengths (``generator.length_set``), every seed;
- position ``t`` of the pool takes the prompt and the output whose ranks
  are the ranks of the two coordinates of point ``t`` of a scrambled
  Sobol' sequence in two dimensions (Owen's nested scrambling, the coins
  from the seed). Of such a sequence every aligned run of ``2**k`` points
  holds exactly one point in each of ``2**k`` equal intervals of either
  coordinate, and one in each box of any dyadic grid with ``2**k`` boxes:
  any 32 requests in a row hold one prompt from each thirty-second of the
  distribution, paired with outputs from all over theirs, whatever the
  seed; which one of its interval each is, where in the run it comes and
  which output it meets is the seed's;
- token ids are drawn as the generator draws them.

Two seeds then differ in the order and the pairing and not in how much a
window holds (``tests/chipbench/test_balanced_pool.py`` counts both).
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

from chipbench.traffic.generator import Request, length_set, rng_for


def sobol_points(n: int) -> Tuple[np.ndarray, np.ndarray, int]:
    """The first ``n`` points of Sobol's sequence in two dimensions as
    whole numbers of ``bits`` bits each: the first coordinate reverses the
    bits of ``t`` (van der Corput), the second multiplies them by Pascal's
    triangle modulo 2 (direction numbers of the polynomial ``x + 1``)."""
    bits = max(1, (n - 1).bit_length())
    t = np.arange(n, dtype=np.int64)
    x = np.zeros(n, np.int64)
    y = np.zeros(n, np.int64)
    direction = 1 << (bits - 1)
    for k in range(bits):
        bit = (t >> k) & 1
        x ^= bit << (bits - 1 - k)
        y ^= bit * direction
        direction ^= direction >> 1
    return x, y, bits


def scramble(x: np.ndarray, bits: int, rng: np.random.Generator) -> np.ndarray:
    """Owen's nested scrambling in base 2: each bit is flipped by a coin
    that depends on the bits above it, so points that share their first
    ``j`` bits still do, and each interval's halves swap or stay."""
    out = np.zeros_like(x)
    for j in range(bits):
        coins = rng.integers(0, 2, size=1 << j)
        bit = ((x >> (bits - 1 - j)) & 1) ^ coins[x >> (bits - j)]
        out |= bit << (bits - 1 - j)
    return out


def _ranks(x: np.ndarray) -> np.ndarray:
    return np.argsort(np.argsort(x))


def closed_pool(mix: Dict[str, Any], seed: int, vocab: int) -> List[Request]:
    """``generator.closed_pool``'s requests (``pool_requests`` of them, the
    same lengths) in the order and pairing described above."""
    n = int(mix["pool_requests"])
    x, y, bits = sobol_points(n)
    order = rng_for(seed, "requests")
    prompts = length_set(mix["prompt_tokens"], n)[
        _ranks(scramble(x, bits, order))]
    outputs = length_set(mix["output_tokens"], n)[
        _ranks(scramble(y, bits, order))]
    rng = rng_for(seed, "tokens")
    return [Request(0.0, rng.integers(0, vocab, size=int(p)).astype(np.int32),
                    int(m)) for p, m in zip(prompts, outputs)]
