"""The capture control of ``monitor/trace.py`` (rings and ``jax.profiler`` over
one interval, on one clock), the always-on compile counter of
``utils/compile_cache.py``, and the engine thread's phase spans
(``serving/frontend.py``: they tile a ``step()``). docs/OBSERVABILITY.md
"Captures"."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.monitor.trace import CAPTURE_ANCHOR, tracer
from deepspeed_tpu.utils import compile_cache


@pytest.fixture(autouse=True)
def _fresh_tracer():
    tracer.reset()
    yield
    tracer.reset()


def _host_events(path, name):
    from jax.profiler import ProfileData
    return sorted((ev.start_ns, ev.duration_ns)
                  for plane in ProfileData.from_file(path).planes
                  if plane.name.startswith("/host:")
                  for line in plane.lines for ev in line.events
                  if ev.name == name)


def _one_capture(directory):
    tracer.capture_start(directory)
    with tracer.span("host/work", k=1):
        time.sleep(0.01)
    t0 = time.perf_counter()
    time.sleep(0.005)
    tracer.add("host/added", t0, time.perf_counter(), lane="host/lane")
    return tracer.capture_stop()


def test_capture_twice_rings_off_before_and_after(tmp_path):
    """Start and stop any number of times in a process; outside a capture the
    rings are as they were (here: off, recording nothing)."""
    assert not tracer.enabled
    tracer.add("before", 0.0, 1.0)
    first = _one_capture(str(tmp_path))
    assert not tracer.enabled
    tracer.add("between", 0.0, 1.0)
    second = _one_capture(str(tmp_path))
    assert not tracer.enabled
    assert first.trace_path != second.trace_path
    for cap in (first, second):
        names = [r[1] for r in cap.records]
        assert names.count("host/work") == 1 and names.count("host/added") == 1
        assert "before" not in names and "between" not in names
        assert len(_host_events(cap.trace_path, CAPTURE_ANCHOR)) == 2
    assert list(tracer.iter_records()) != []      # the rings keep what they held
    assert "between" not in [r[1] for r in tracer.iter_records()]


def test_capture_restores_enabled_and_filters_by_interval(tmp_path):
    """A tracer that was on (``DSTPU_TRACE``) stays on, and the capture holds
    only the records of its own interval."""
    tracer.configure(enabled=True)
    tracer.add("old", time.perf_counter() - 5.0, time.perf_counter() - 4.0)
    cap = _one_capture(str(tmp_path))
    assert tracer.enabled
    assert "old" not in [r[1] for r in cap.records]
    assert "old" in [r[1] for r in tracer.iter_records()]


def test_records_are_mapped_by_the_anchors(tmp_path):
    """A context-manager span is in the profiler's trace twice over: as the
    ``TraceAnnotation`` it entered and as the ring record the anchors mapped.
    The two agree; the post-hoc ``add`` site has only the mapping."""
    cap = _one_capture(str(tmp_path))
    anchors = _host_events(cap.trace_path, CAPTURE_ANCHOR)
    assert cap.start_ns == anchors[0][0] and cap.stop_ns == anchors[1][0]
    (ann_start, ann_dur), = _host_events(cap.trace_path, "host/work")
    rec = next(r for r in cap.records if r[1] == "host/work")
    kind, name, t0_ns, t1_ns, lane, args, thread = rec
    assert kind == "X" and args == {"k": 1} and thread == "MainThread"
    assert abs(t0_ns - ann_start) < 200e3            # 0.2 ms on a busy CPU box
    assert abs((t1_ns - t0_ns) - ann_dur) < 200e3
    assert cap.start_ns < t0_ns < t1_ns < cap.stop_ns
    added = next(r for r in cap.records if r[1] == "host/added")
    assert added[4] == "host/lane" and added[2] >= t1_ns
    assert not _host_events(cap.trace_path, "host/added")
    # drift is the second anchor's correction; a pure offset would be off by
    # the skew at the interval's end, and both are small
    assert abs(cap.drift - 1.0) < 1e-3 and abs(cap.skew_ns) < 1e6
    assert cap.to_ns(cap.perf_start) == cap.start_ns
    assert cap.spans("host/work") == [("host/work", t0_ns, t1_ns)]


def test_capture_counters_are_deltas(tmp_path):
    tracer.bump("unit/things", 5)
    tracer.capture_start(str(tmp_path))
    tracer.bump("unit/things", 2)
    tracer.bump("unit/new")
    cap = tracer.capture_stop()
    assert cap.counters["unit/things"] == 2 and cap.counters["unit/new"] == 1
    assert tracer.totals["unit/things"] == 7


def test_capture_from_a_helper_thread_and_misuse(tmp_path):
    with pytest.raises(RuntimeError):
        tracer.capture_stop()
    box = {}

    def body():
        tracer.capture_start(str(tmp_path))
        time.sleep(0.02)
        box["cap"] = tracer.capture_stop()

    helper = threading.Thread(target=body, name="helper")
    helper.start()
    deadline = time.time() + 10
    while not tracer.enabled and time.time() < deadline:
        time.sleep(0.001)
    with tracer.span("main/while_captured"):
        pass
    helper.join(timeout=30)
    assert not helper.is_alive() and not tracer.enabled
    assert "main/while_captured" in [r[1] for r in box["cap"].records]
    tracer.capture_start(str(tmp_path))
    with pytest.raises(RuntimeError):
        tracer.capture_start(str(tmp_path))
    tracer.capture_stop()


def test_compile_counter_against_a_forced_recompile(tmp_path):
    """The listener counts every program that was not ready — a module-level
    jit and an eager operation alike — and, while tracing, records the stall
    as a span on the thread it stalled, inside the enclosing span."""
    compile_cache.install_compile_listener()
    compile_cache.install_compile_listener()          # idempotent
    f = jax.jit(lambda x: x * 3 + 1)
    three, five = np.ones((3,), np.float32), np.ones((5,), np.float32)
    f(three).block_until_ready()
    base = compile_cache.backend_compiles()
    f(three).block_until_ready()                      # ready: not counted
    assert compile_cache.backend_compiles() == base
    tracer.configure(enabled=True)
    with tracer.span("unit/step"):
        f(five).block_until_ready()                   # a new shape recompiles
    assert compile_cache.backend_compiles() == base + 1
    assert tracer.totals["compile/backend_compile_s"] > 0
    spans = {r[1]: r for r in tracer.iter_records()}
    c, s = spans["compile/backend"], spans["unit/step"]
    assert s[2] <= c[2] <= c[3] <= s[3]
    before = compile_cache.backend_compiles()
    (jnp.ones((7,)) + 2).block_until_ready()          # an eager helper
    assert compile_cache.backend_compiles() > before


# --------------------------------------------------------------------------- #
# the engine thread's phases
# --------------------------------------------------------------------------- #

def _frontend():
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig.tiny(vocab_size=128, max_position_embeddings=256)
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]
    engine = InferenceEngineV2(model=model, model_parameters=params, config={
        "dtype": jnp.float32,
        "state_manager": {"max_tracked_sequences": 8,
                          "max_ragged_sequence_count": 4,
                          "max_ragged_batch_size": 96, "max_context": 176,
                          "prefill_chunk_size": 32},
        "kv_cache": {"block_size": 16, "num_blocks": 24},
        "serving": {"decode_slice": 4, "idle_wait_s": 0.005,
                    "classes": [{"name": "hi", "priority": 2,
                                 "ttft_slo_ms": 1e6, "tbt_slo_ms": 1e6}]}})
    return engine, engine.serving_frontend()


def test_engine_thread_phases_tile_a_step():
    """Driven synchronously, every ``step()`` is covered by its phase spans
    on the ``serve/loop`` lane: each begins where the last ended, none
    overlap, and a prefill pass says what it held."""
    engine, fe = _frontend()
    rng = np.random.RandomState(0)
    tracer.configure(enabled=True)
    handles = [fe.submit(rng.randint(0, 128, size=(n,)).astype(np.int32),
                         priority="hi", max_new_tokens=6) for n in (40, 9)]
    bounds = []
    for _ in range(12):
        t0 = time.perf_counter()
        fe.step()
        bounds.append((t0, time.perf_counter()))
    assert all(h.finished for h in handles)
    tracer.enabled = False
    loop = sorted((r for r in tracer.iter_records() if r[4] == "serve/loop"),
                  key=lambda r: r[2])
    names = {r[1] for r in loop}
    assert names == {"serve/loop/control", "serve/loop/admission",
                     "serve/prefill/pass", "serve/loop/decode_slice"}
    for a, b in zip(loop, loop[1:]):
        assert a[3] <= b[2]                           # no overlap
    for t0, t1 in bounds:
        mine = [r for r in loop if t0 <= r[2] and r[3] <= t1]
        assert mine and mine[0][1] == "serve/loop/control"
        assert all(a[3] == b[2] for a, b in zip(mine, mine[1:]))   # no gap
        assert mine[-1][3] - mine[0][2] > 0.8 * (t1 - t0) - 1e-3
    passes = [r for r in loop if r[1] == "serve/prefill/pass"]
    assert sum(r[5]["tokens"] for r in passes) == 49
    assert all(r[5]["slots"] >= 1 and r[5]["kind"] in ("packed", "paged")
               for r in passes)
    boots = [r for r in tracer.iter_records()
             if r[1] == "serve/decode/bootstrap"]
    slices = [r for r in loop if r[1] == "serve/loop/decode_slice"]
    assert len(boots) == len(slices)
    assert all(any(s[2] <= b[2] and b[3] <= s[3] for s in slices)
               for b in boots)
    fe.close()


def test_a_prefill_pass_says_what_its_chunks_held(tmp_path):
    """``serve/prefill/pass`` carries, slot by live slot, the prompt tokens a
    chunk holds (``ntok``) and the keys it reads from pages before its first
    (``cached``): nothing in a packed pass, the prefix in a long prompt's
    later chunks — the earlier pass's and, for the same pass's later slots,
    the slots before them. Plain ints in tuples; the export is valid JSON."""
    import json
    engine, fe = _frontend()
    rng = np.random.RandomState(1)
    tracer.configure(enabled=True)
    handles = [fe.submit(rng.randint(0, 128, size=(n,)).astype(np.int32),
                         priority="hi", max_new_tokens=2) for n in (168,)]
    for _ in range(8):
        fe.step()
    assert all(h.finished for h in handles)
    tracer.enabled = False
    passes = [r[5] for r in sorted(tracer.iter_records(), key=lambda r: r[2])
              if r[1] == "serve/prefill/pass"]
    assert [a["kind"] for a in passes] == ["packed", "paged"]
    first, second = passes
    assert first["ntok"] == (32, 32, 32) and first["cached"] == (0, 0, 0)
    assert second["ntok"] == (32, 32, 8)
    assert second["cached"] == (96, 128, 160)
    for a in passes:
        assert sum(a["ntok"]) == a["tokens"]
        assert len(a["ntok"]) == len(a["cached"]) == a["slots"]
        assert all(type(v) is int for v in a["ntok"] + a["cached"])
    path = tracer.export(str(tmp_path / "trace.json"))
    names = {ev["name"]: ev.get("args") for ev in json.load(open(path))[
        "traceEvents"] if ev["ph"] == "B"}
    assert names["serve/prefill/pass"]["cached"] == [96, 128, 160]
    assert isinstance(names["serve/decode/step"]["ctx"], int)
    fe.close()


def test_phases_cost_nothing_recorded_when_off():
    engine, fe = _frontend()
    h = fe.submit(np.arange(5, dtype=np.int32), priority="hi",
                  max_new_tokens=3)
    for _ in range(6):
        fe.step()
    assert h.finished and fe._loop_t == 0.0
    assert list(tracer.iter_records()) == []
    assert engine.backend_compiles >= engine.compiles > 0
    fe.close()


def test_idle_wait_is_a_span_on_the_engine_thread():
    engine, fe = _frontend()
    tracer.configure(enabled=True)
    with fe:
        time.sleep(0.05)
    tracer.enabled = False
    idle = [r for r in tracer.iter_records() if r[1] == "serve/loop/idle"]
    assert idle and all(r[4] == "serve/loop" for r in idle)
