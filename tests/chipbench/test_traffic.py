"""The traffic generator and the two loops that send its requests."""

import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.reduce import latency  # noqa: E402
from chipbench.traffic import generator, replay  # noqa: E402

MIX = {"arrivals": {"process": "poisson", "rate_per_s": 4.0}, "ramp_s": 5.0,
       "prompt_tokens": {"dist": "lognormal", "median": 512, "sigma": 0.9,
                         "min": 64, "max": 3072},
       "output_tokens": {"dist": "lognormal", "median": 96, "sigma": 0.7,
                         "min": 16, "max": 384}}
BIG_SEED = 2**31 + 12345


def shape(reqs):
    return ([round(r.due_s, 9) for r in reqs], [len(r.prompt) for r in reqs],
            [r.max_new_tokens for r in reqs])


def test_same_seed_same_traffic_other_seed_same_work_in_another_order():
    a = generator.open_schedule(MIX, 45, BIG_SEED, vocab=32000)
    b = generator.open_schedule(MIX, 45, BIG_SEED, vocab=32000)
    c = generator.open_schedule(MIX, 45, 7, vocab=32000)
    assert shape(a) == shape(b)
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    assert shape(a) != shape(c)
    assert len(a) == len(c) == 200                  # 4/s over 5 + 45 s
    # the window holds the same requests at the same gaps whatever the seed
    # (and so does the ramp): no seed moves a long prompt out of the window
    for part in (True, False):
        pa = [r for r in a if r.measured is part]
        pc = [r for r in c if r.measured is part]
        assert len(pa) == len(pc) == (180 if part else 20)
        for k in (1, 2):
            assert sorted(shape(pa)[k]) == sorted(shape(pc)[k])
        gaps = lambda r: np.diff(shape(r)[0])       # noqa: E731
        assert gaps(pa).min() > 0
        # all gaps but the last, which runs to the part's end
        gap_set = generator.gap_set(MIX["arrivals"], len(pa))
        assert np.abs(gaps(pa)[:, None] - gap_set[None, :]).min(1).max() < 1e-9
    window = [r for r in a if r.measured]
    assert [r.measured for r in a] == [False] * 20 + [True] * 180
    assert a[0].due_s == 0.0 and window[0].due_s == pytest.approx(5.0)
    assert 5.0 < window[-1].due_s < 50.0
    assert generator.gap_set(MIX["arrivals"], 180).sum() == pytest.approx(45.0)


def test_every_seed_orders_arrivals_and_lengths_anew():
    """No knob fixes the order: six seeds give six orders of the same gaps
    and the same lengths, and the lengths are permuted apart from the gaps."""
    runs = [generator.open_schedule(MIX, 45, s, vocab=32000)
            for s in range(101, 107)]
    dues, prompts, outputs = zip(*(shape(r) for r in runs))
    assert len(set(map(tuple, dues))) == 6
    assert len(set(map(tuple, prompts))) == 6
    assert len(set(map(tuple, outputs))) == 6
    assert len({tuple(sorted(p)) for p in prompts}) == 1
    with pytest.raises(ValueError):
        generator.gap_set({"process": "gamma", "rate_per_s": 2.0}, 10)
    with pytest.raises(ValueError):
        generator.length_set({"dist": "fixed", "value": 8}, 10)


def test_lengths_follow_the_clipped_distribution():
    n = generator.length_set(MIX["prompt_tokens"], 401)
    assert n.min() == 64 and n.max() == 3072
    assert np.median(n) == pytest.approx(512, abs=2)
    u = generator.length_set({"dist": "uniform", "min": 128, "max": 1024}, 256)
    assert u.min() >= 128 and u.max() <= 1024
    assert u.mean() == pytest.approx(576, abs=2)
    pool = generator.closed_pool(dict(MIX, pool_requests=64), 3, vocab=100)
    assert len(pool) == 64 and all(0 <= r.prompt.min() and r.prompt.max() < 100
                                   for r in pool)
    batches = generator.train_batches({"seq_len": 16, "distinct_batches": 3},
                                      2, BIG_SEED, vocab=50)
    assert len(batches) == 3 and batches[0]["input_ids"].shape == (2, 16)
    assert not (batches[0]["input_ids"] == batches[1]["input_ids"]).all()


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now

    def sleep(self, s):
        self.now += s


class FakeServer:
    """Answers ``service_s`` after a request is submitted; ``submit`` itself
    takes ``submit_s`` of the sender's time (a sender that cannot keep up)."""

    def __init__(self, clock, service_s=0.05, submit_s=0.0):
        self.clock, self.service_s, self.submit_s = clock, service_s, submit_s
        self.inflight_seen = []
        self.handles = []

    def submit(self, prompt, max_new_tokens):
        self.clock.sleep(self.submit_s)
        t = self.clock()
        done_at = t + self.service_s
        h = _Handle(SimpleNamespace(
            arrival_t=t, admit_t=t, ttft_ms=1e3 * self.service_s,
            tbt_ms=[0.0] * (max_new_tokens - 1), status="finished",
            tokens=[0] * max_new_tokens), done_at, self.clock)
        self.handles.append(h)
        self.inflight_seen.append(sum(not x.finished for x in self.handles))
        return h


class _Handle:
    def __init__(self, fields, done_at, clock):
        self.__dict__.update(fields.__dict__)
        self._done_at, self._clock = done_at, clock

    @property
    def finished(self):
        return self._clock() >= self._done_at


def test_a_late_generator_raises_ttft_and_does_not_hide_it():
    reqs = [generator.Request(0.1 * i, np.zeros(4, np.int32), 2)
            for i in range(10)]
    on_time, late = FakeClock(), FakeClock()
    a = replay.replay_open(FakeServer(on_time).submit, reqs, on_time() + 1.0,
                           clock=on_time, sleep=on_time.sleep)
    # the same server, but each submit costs the sender 0.3 s: it falls behind
    b = replay.replay_open(FakeServer(late, submit_s=0.3).submit, reqs,
                           late() + 1.0, clock=late, sleep=late.sleep)
    assert max(latency.lateness_ms(s) for s in a) == pytest.approx(0.0, abs=1e-6)
    assert [latency.ttft_ms(s) for s in a] == pytest.approx([50.0] * 10)
    # counted from submit (the original loadgen) both would read 50 ms
    assert all(s.handle.ttft_ms == 50.0 for s in b)
    ttft_b = [latency.ttft_ms(s) for s in b]
    assert ttft_b[0] == pytest.approx(350.0)
    assert ttft_b[-1] == pytest.approx(50.0 + 1e3 * (10 * 0.3 - 0.9))
    assert latency.lateness_ms(b[-1]) == pytest.approx(2100.0)
    assert [s.due_t for s in a] == pytest.approx(
        [101.0 + 0.1 * i for i in range(10)])


def test_open_loop_marks_run_in_order_between_sends():
    clock, seen = FakeClock(), []
    reqs = [generator.Request(t, np.zeros(2, np.int32), 1) for t in (0.5, 1.5)]
    t0 = clock()
    replay.replay_open(FakeServer(clock).submit, reqs, t0,
                       marks=[(2.0, lambda: seen.append(("end", clock() - t0))),
                              (1.0, lambda: seen.append(("start", clock() - t0)))],
                       clock=clock, sleep=clock.sleep)
    assert seen == [("start", pytest.approx(1.0)), ("end", pytest.approx(2.0))]


@pytest.mark.parametrize("clients", [1, 4, 32])
def test_closed_loop_never_has_more_than_its_clients_in_flight(clients):
    clock = FakeClock()
    server = FakeServer(clock, service_s=0.05)
    pool = [generator.Request(0.0, np.zeros(3, np.int32), 2) for _ in range(5)]
    marks_seen = []
    sent = replay.run_closed(server.submit, pool, clients, until=clock() + 1.0,
                             marks=[(clock() + 0.5, lambda: marks_seen.append(clock()))],
                             clock=clock, sleep=clock.sleep, poll_s=0.01)
    assert max(server.inflight_seen) == clients
    assert len(sent) >= clients * 10          # each client went round often
    assert all(s.sent_t < 101.0 for s in sent)           # nothing after until
    assert marks_seen and marks_seen[0] == pytest.approx(100.5, abs=0.02)
    assert replay.drain(sent, 1.0, clock=clock, sleep=clock.sleep)
    stuck = SimpleNamespace(handle=SimpleNamespace(finished=False))
    assert not replay.drain([stuck], 0.1, clock=clock, sleep=clock.sleep)
