"""utils/caching: the shape-bucketing helper and the bounded LRU that every
long-lived serving cache (decode-step programs, verify-step programs) rides."""

import threading

import pytest

from deepspeed_tpu.utils.caching import LRUCache, next_pow2


# --------------------------------------------------------------------------- #
# next_pow2 — the canonical bucket function
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("n,expect", [
    (0, 1),            # zero rows still needs a one-row program
    (1, 1),
    (2, 2),
    (3, 4),
    (4, 4),            # 2^k stays 2^k ...
    (8, 8),
    (1024, 1024),
    (5, 8),            # ... 2^k + 1 jumps to 2^(k+1)
    (9, 16),
    (1025, 2048),
    (7, 8),
])
def test_next_pow2(n, expect):
    assert next_pow2(n) == expect


def test_next_pow2_is_monotone_and_bounding():
    prev = 0
    for n in range(200):
        b = next_pow2(n)
        assert b >= max(1, n)           # always big enough
        assert b < 2 * max(1, n) + 1    # never more than ~2x waste
        assert b >= prev                # monotone: shrinking sets never grow
        prev = b


# --------------------------------------------------------------------------- #
# LRUCache — eviction, key identity, in-flight safety
# --------------------------------------------------------------------------- #

def test_lru_eviction_at_capacity_is_oldest_first():
    built = []
    cache = LRUCache(maxsize=2)
    for k in ("a", "b", "c"):
        cache.get_or_create(k, lambda k=k: built.append(k) or k.upper())
    assert built == ["a", "b", "c"]
    assert len(cache) == 2
    assert "a" not in cache and "b" in cache and "c" in cache
    # re-requesting the evicted key rebuilds (and evicts the now-oldest "b")
    assert cache.get_or_create("a", lambda: built.append("a2") or "A2") == "A2"
    assert built[-1] == "a2"
    assert "b" not in cache


def test_lru_hit_refreshes_recency():
    cache = LRUCache(maxsize=2)
    cache.get_or_create("a", lambda: 1)
    cache.get_or_create("b", lambda: 2)
    cache.get_or_create("a", lambda: pytest.fail("hit must not rebuild"))
    cache.get_or_create("c", lambda: 3)     # evicts "b" (LRU), not "a"
    assert "a" in cache and "b" not in cache


def test_lru_eviction_never_invalidates_inflight_value():
    """The engine contract: the pipeline (_decode_step_prog) takes a strong
    reference to the cached program BEFORE dispatching, so eviction (another
    key landing while the program is mid-flight) must never break the held
    value. Python reference semantics guarantee it — this pins the contract
    so a future swap to weakrefs/explicit-free trips here first."""
    cache = LRUCache(maxsize=1)
    prog = cache.get_or_create("bucket4", lambda: (lambda x: x * 2))
    cache.get_or_create("bucket8", lambda: (lambda x: x * 3))   # evicts b4
    assert "bucket4" not in cache
    assert prog(21) == 42                    # the held executable still runs
    # and re-creating the evicted key yields a fresh build, not the old one
    prog2 = cache.get_or_create("bucket4", lambda: (lambda x: x * 5))
    assert prog2(1) == 5 and prog(1) == 2


def test_lru_racing_cold_key_builds_once():
    calls = []
    cache = LRUCache(maxsize=4)
    barrier = threading.Barrier(4)

    def worker():
        def factory():
            calls.append(1)
            return "v"
        barrier.wait()
        assert cache.get_or_create("k", factory) == "v"

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(calls) == 1
