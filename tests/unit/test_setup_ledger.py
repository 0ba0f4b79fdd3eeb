"""Set-up from inside the process (docs/OBSERVABILITY.md, "Set-up and
compiles"): ``tracer.stage`` charges a start's seconds to stages, and the
compile listener of ``utils/compile_cache.py`` charges what jax traces,
lowers and compiles to the phase of the open stage and to the program jax
names — always on, each second once."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.monitor.trace import STAGE_PHASE, tracer
from deepspeed_tpu.utils import compile_cache

KINDS = ("trace_s", "lower_s", "backend_s", "programs")


@pytest.fixture(autouse=True)
def _fresh_tracer():
    """Tracing off, no stage open, phase ``before``, and no ``setup/*`` total
    left by an earlier test of this process (``reset`` keeps the totals)."""
    compile_cache.install_compile_listener()
    tracer.reset()
    with tracer._totals_lock:
        for name in [n for n in tracer.totals if n.startswith("setup/")]:
            del tracer.totals[name]
    yield
    tracer.reset()


def _totals(prefix):
    return {k: v for k, v in tracer.totals.items() if k.startswith(prefix)}


def _gained(before, prefix):
    return {k: v - before.get(k, 0.0) for k, v in _totals(prefix).items()
            if v != before.get(k, 0.0)}


def _fresh_jit(name, body=lambda x: x * 3 + 1):
    """A jitted function jax has never seen, named ``name``; its trace takes
    longer than the shortest compile span that is drawn."""
    def fn(x):
        time.sleep(2 * compile_cache.SPAN_MIN_S)
        return body(x)
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


# --------------------------------------------------------------------------- #
# phases and programs
# --------------------------------------------------------------------------- #

def test_first_call_inside_a_warmup_stage_is_charged_to_it_and_to_f():
    f = _fresh_jit("f")
    before = _totals("compile/")
    rows = compile_cache.programs().get("f", {}).get("warmup", [0, 0, 0, 0])
    with tracer.stage("warmup"):
        f(np.ones((3,), np.float32)).block_until_ready()
    gained = _gained(before, "compile/warmup/")
    assert set(gained) == {f"compile/warmup/{k}" for k in KINDS}
    assert all(v > 0 for v in gained.values())
    assert gained["compile/warmup/programs"] == 1
    row = compile_cache.programs()["f"]["warmup"]
    assert row[0] - rows[0] == 3            # traced, lowered, compiled
    assert all(row[i] > rows[i] for i in (1, 2, 3))
    assert tracer.totals["setup/warmup_s"] >= sum(
        gained[f"compile/warmup/{k}"] for k in KINDS[:3])
    # ready now: a second call is charged to nobody
    before = _totals("compile/")
    f(np.ones((3,), np.float32)).block_until_ready()
    assert _gained(before, "compile/") == {}


@pytest.mark.parametrize("warmed, phase", [(False, "before"),
                                           (True, "traffic")])
def test_with_no_stage_open_the_phase_is_before_then_traffic(warmed, phase):
    if warmed:
        with tracer.stage("warmup"):
            pass
    assert tracer.phase() == phase
    before = _totals("compile/")
    _fresh_jit(f"g_{phase}")(np.ones((5,), np.float32)).block_until_ready()
    gained = _gained(before, "compile/")
    assert gained[f"compile/{phase}/programs"] == 1
    assert gained["compile/backend_compiles"] == 1      # the old four stay
    assert {k.split("/")[1] for k in gained} == {phase, "backend_compiles",
                                                 "backend_compile_s"}
    assert list(compile_cache.programs()[f"g_{phase}"]) == [phase]


@pytest.mark.parametrize("stage, phase", sorted(STAGE_PHASE.items()))
def test_every_stage_has_one_of_two_phases(stage, phase):
    assert phase in ("build", "warmup")
    with tracer.stage(stage):
        assert tracer.phase() == phase
        with tracer.stage("child"):
            assert tracer.phase() == phase
    # only a warm-up or a first step turns "before" into "traffic"
    assert tracer.phase() == ("traffic" if stage in ("warmup", "first_step")
                              else "before")


def test_a_jit_that_calls_a_jit_is_charged_its_seconds_once():
    nap = 0.2

    def slow(x):
        time.sleep(nap)             # python time inside inner's trace
        return x + 1
    inner = _fresh_jit("inner_once", slow)
    outer = _fresh_jit("outer_once", lambda x: inner(x) * 2)
    before = _totals("compile/")
    t0 = time.perf_counter()
    with tracer.stage("warmup"):
        outer(np.ones((3,), np.float32)).block_until_ready()
    wall = time.perf_counter() - t0
    gained = _gained(before, "compile/warmup/")
    progs = compile_cache.programs()
    assert progs["inner_once"]["warmup"][1] >= nap
    # the outer trace held the inner one: jax reported >= nap for both
    assert progs["outer_once"]["warmup"][1] < nap
    assert nap <= gained["compile/warmup/trace_s"] < nap + 0.1
    assert sum(gained[f"compile/warmup/{k}"] for k in KINDS[:3]) <= wall
    assert gained["compile/warmup/programs"] == 1       # one module


@pytest.mark.parametrize("depth", [0, 40])
def test_with_stack_room_runs_the_call_under_one_frame_larger_than_a_chunk(
        depth):
    """CPython keeps a thread's frames in chunks of 16 KiB; a frame of 65,536
    locals cannot fit one, so it is given a chunk of its own size and what it
    calls runs in the rest of it — from whatever depth it is called. The
    call's value comes back and its exception passes through."""
    import sys

    def at(n, f):
        return compile_cache.with_stack_room(f) if n == 0 else at(n - 1, f)

    frame = at(depth, lambda: sys._getframe(1))
    assert frame.f_code.co_nlocals * 8 > (1 << 14) * 16
    assert at(depth, lambda: 7) == 7
    with pytest.raises(ZeroDivisionError):
        at(depth, lambda: 1 / 0)


def test_the_program_table_stops_at_512_names(monkeypatch):
    assert compile_cache.MAX_PROGRAMS == 512
    full = {f"p{i}": {} for i in range(compile_cache.MAX_PROGRAMS - 1)}
    full["kept_fn"] = {}
    monkeypatch.setattr(compile_cache, "_programs", full)
    for name in ("one_over", "kept_fn", "two_over"):
        _fresh_jit(name)(np.ones((2,), np.float32)).block_until_ready()
    progs = compile_cache.programs()
    assert len(progs) == compile_cache.MAX_PROGRAMS + 1
    assert "one_over" not in progs and "two_over" not in progs
    assert progs["kept_fn"]["before"][0] == 3       # a name it has keeps a row
    assert progs[compile_cache.OTHER]["before"][0] >= 6
    assert progs[compile_cache.OTHER]["before"][3] > 0


# --------------------------------------------------------------------------- #
# stages
# --------------------------------------------------------------------------- #

def test_a_child_is_charged_inside_its_parent():
    with tracer.stage("engine_init") as parent:
        time.sleep(0.02)
        with tracer.stage("shard_weights") as child:
            time.sleep(0.03)
        with tracer.stage("shard_weights"):     # the same child again: adds
            time.sleep(0.01)
    setup = _totals("setup/")
    assert set(setup) == {"setup/engine_init_s",
                          "setup/engine_init/shard_weights_s"}
    assert child.seconds >= 0.03
    assert setup["setup/engine_init/shard_weights_s"] >= 0.04
    assert setup["setup/engine_init_s"] == parent.seconds >= 0.06
    assert setup["setup/engine_init/shard_weights_s"] \
        <= setup["setup/engine_init_s"]


def test_a_top_level_stage_inside_another_leaves_it_net():
    t0 = time.perf_counter()
    with tracer.stage("first_step"):
        time.sleep(0.02)
        with tracer.stage("state_build"):
            time.sleep(0.03)
        with tracer.stage("remat_fit"):
            with tracer.stage("rung0"):
                time.sleep(0.02)
                with tracer.stage("state_build"):   # top-level, two deep
                    time.sleep(0.01)
    wall = time.perf_counter() - t0  # jaxlint: disable=JL001 -- sleeps, no device work
    setup = _totals("setup/")
    assert set(setup) == {"setup/first_step_s", "setup/state_build_s",
                          "setup/remat_fit_s", "setup/remat_fit/rung0_s"}
    assert setup["setup/state_build_s"] >= 0.04
    assert 0.02 <= setup["setup/remat_fit/rung0_s"] \
        <= setup["setup/remat_fit_s"] < 0.03 + 0.02
    assert 0.02 <= setup["setup/first_step_s"] < 0.02 + 0.02
    tops = sum(setup[f"setup/{s}_s"] for s in STAGE_PHASE if
               f"setup/{s}_s" in setup)
    assert tops == pytest.approx(wall, abs=5e-3)    # they add up to wall time


def test_a_stage_closes_on_an_exception():
    with pytest.raises(KeyError):
        with tracer.stage("warmup"):
            with tracer.stage("passes"):
                raise KeyError("x")
    assert set(_totals("setup/")) == {"setup/warmup_s",
                                      "setup/warmup/passes_s"}
    assert tracer.phase() == "traffic"
    with tracer.stage("engine_init"):
        pass
    assert "setup/engine_init_s" in tracer.totals   # not a child of a ghost


def test_with_tracing_off_a_stage_and_a_compile_make_no_record():
    with tracer.stage("warmup"):
        _fresh_jit("quiet")(np.ones((3,), np.float32)).block_until_ready()
    assert tracer.totals["setup/warmup_s"] > 0
    assert list(tracer.iter_records()) == []


def test_with_tracing_on_stages_and_compile_spans_name_their_program(
        monkeypatch):
    # (well under what lowering and compiling take on any host)
    monkeypatch.setattr(compile_cache, "SPAN_MIN_S", 2e-4)
    tracer.configure(enabled=True)
    with tracer.stage("warmup"):
        with tracer.stage("decode_grid"):
            _fresh_jit("loud")(np.ones((3,), np.float32)).block_until_ready()
    _fresh_jit("later")(np.ones((3,), np.float32)).block_until_ready()
    # an event shorter than that (a primitive traced inside a larger trace,
    # microseconds, thousands a program) is counted, not drawn
    compile_cache._on_duration(compile_cache._TRACE, 1e-5, fun_name="tiny_op")
    assert compile_cache.programs()["tiny_op"]["traffic"][:2] == [1, 1e-5]
    assert "tiny_op" not in [(r[5] or {}).get("program")
                             for r in tracer.iter_records()]
    tracer.enabled = False
    recs = list(tracer.iter_records())
    spans = {r[1]: r for r in recs if (r[5] or {}).get("program") == "loud"}
    assert set(spans) == {"compile/trace", "compile/lower", "compile/backend"}
    outer = next(r for r in recs if r[1] == "setup/warmup")
    grid = next(r for r in recs if r[1] == "setup/warmup/decode_grid")
    assert outer[4] == grid[4] == "setup"
    assert outer[2] <= grid[2] <= grid[3] <= outer[3]
    for r in spans.values():
        assert r[4] == "setup"                      # inside the stage's lane
        assert grid[2] <= r[2] <= r[3] <= grid[3]
    a, b, c = (spans[f"compile/{k}"] for k in ("trace", "lower", "backend"))
    assert a[3] <= b[3] <= c[3]
    # after set-up a compile span stays on its thread's own track
    late = [r for r in recs if (r[5] or {}).get("program") == "later"]
    assert len(late) == 3 and all(r[4] is None for r in late)
    # ... and the exported timeline nests them (B/E pairs on one track)
    events = [e for e in tracer._events() if e.get("ph") in "BE"]
    setup_tid = next(e["tid"] for e in events if e["name"] == "setup/warmup")
    lane = [e["name"] for e in events if e["tid"] == setup_tid
            and e["ph"] == "B"]
    assert lane[:2] == ["setup/warmup", "setup/warmup/decode_grid"]
    assert {"compile/trace", "compile/lower", "compile/backend"} <= set(lane)


def test_setup_events_and_summary_carry_the_names():
    with tracer.stage("warmup"):
        _fresh_jit("summed")(np.ones((3,), np.float32)).block_until_ready()
    events = tracer.setup_events(step=4)
    names = [n for n, _, _ in events]
    assert "setup/warmup_s" in names and "compile/warmup/trace_s" in names
    assert all(n.startswith(("setup/", "compile/")) for n in names)
    assert all(isinstance(v, float) and s == 4 for _, v, s in events)
    line = compile_cache.setup_summary(top=10 ** 6)
    for word in ("set-up by stage, s: warmup ", "compiles by phase: ",
                 "warmup trace ", " programs", "cache loads ",
                 "costliest programs, s: ", "summed "):
        assert word in line


# --------------------------------------------------------------------------- #
# the engines
# --------------------------------------------------------------------------- #

class _Sink:
    def __init__(self):
        self.events = []

    def write_events(self, event_list):
        self.events.extend(event_list)


@pytest.fixture(scope="module")
def serving():
    """A tiny engine whose constructor warms it up, and what its set-up left
    in the totals (the tracer is reset around every test)."""
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from deepspeed_tpu.utils.logging import logger
    cfg = LlamaConfig.tiny(vocab_size=128, max_position_embeddings=128)
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]
    tracer.reset()
    before = dict(tracer.totals)
    lines = []
    log = logger.log
    logger.log = lambda level, msg, *a, **k: lines.append(str(msg))
    try:
        engine = InferenceEngineV2(model=model, model_parameters=params, config={
            "dtype": jnp.float32,
            "state_manager": {"max_tracked_sequences": 4,
                              "max_ragged_sequence_count": 4,
                              "max_ragged_batch_size": 32, "max_context": 128},
            "kv_cache": {"block_size": 16},
            "compile": {"warmup": True, "warmup_buckets": [1, 2, 4]}})
    finally:
        logger.log = log
    gained = {k: v - before.get(k, 0.0) for k, v in tracer.totals.items()
              if v != before.get(k, 0.0)}
    return engine, gained, lines


def test_serving_engine_leaves_its_stages(serving):
    _, gained, _ = serving
    setup = {k: v for k, v in gained.items() if k.startswith("setup/")}
    assert set(setup) == {
        "setup/engine_init_s", "setup/engine_init/shard_weights_s",
        "setup/engine_init/kv_alloc_s", "setup/warmup_s",
        "setup/warmup/passes_s", "setup/warmup/decode_grid_s",
        "setup/warmup/page_movers_s", "setup/warmup/sampler_s"}
    assert all(v > 0 for v in setup.values())
    for parent in ("engine_init", "warmup"):
        children = sum(v for k, v in setup.items()
                       if k.startswith(f"setup/{parent}/"))
        assert children <= setup[f"setup/{parent}_s"]


def test_serving_engine_compiles_land_in_build_and_warmup(serving):
    engine, gained, _ = serving
    assert gained["compile/warmup/programs"] >= engine.compiles > 0
    for phase, stage in (("build", "engine_init"), ("warmup", "warmup")):
        spent = sum(gained.get(f"compile/{phase}/{k}", 0.0)
                    for k in KINDS[:3])
        assert 0 < spent <= gained[f"setup/{stage}_s"]
    assert not [k for k in gained if k.startswith("compile/traffic/")]
    assert "serve_decode_step" in compile_cache.programs()


def test_serving_engine_says_where_set_up_went_once(serving):
    engine, _, lines = serving
    said = [l for l in lines if "set-up by stage" in l]
    assert len(said) == 1
    for word in ("engine_init", "warmup/decode_grid", "compiles by phase",
                 "warmup trace", "cache loads", "costliest programs"):
        assert word in said[0]
    assert engine.warmup(buckets=[1, 2, 4]) == 0        # a rejoin warms again
    assert engine._setup_logged


#: module-level helpers of the sampler that a first decode run still builds
#: on this mesh (ROADMAP S1: warm-up hands them other arguments than traffic
#: does) — what ``compile/traffic/*`` exists to name
SAMPLER_HELPERS = {"serve_sample_rows", "serve_place_rows", "_threefry_split",
                   "_unstack"}


def test_a_decode_run_after_warm_up_compiles_nothing_process_wide(serving):
    """ROADMAP S1's invariant on the process-wide count, module-level jits
    and eager helpers included: none of the engine's programs is built under
    traffic, what a FIRST run builds is named, and a run like one before it
    builds nothing at all."""
    engine, _, _ = serving
    prompts = [np.array([3, 14, 15, 92, 6], np.int32),
               np.array([27, 18, 28, 18], np.int32)]
    with tracer.stage("warmup"):
        pass                        # the tracer was reset: traffic again

    def built():
        # backend seconds under traffic, by name: a name GROWS when a program
        # of it is built, whatever this process built under it before (the
        # suite's workers run other files first, and which ones varies)
        return {name: phases.get("traffic", [0, 0, 0, 0])[3]
                for name, phases in compile_cache.programs().items()}

    def grown(since):
        return {name for name, s in built().items() if s > since.get(name, 0)}

    known, engine_programs = built(), engine.compiles
    for uids in ([0, 1], [2, 3]):
        engine.put(uids, prompts)
        before = tracer.totals.get("compile/traffic/programs", 0.0)
        got = engine.decode_pipeline(uids).run(6)
        assert got.shape == (2, 6)
        gained = tracer.totals.get("compile/traffic/programs", 0.0) - before
        if uids == [0, 1]:
            assert grown(known) <= SAMPLER_HELPERS
            assert gained == len(grown(known))
        else:
            assert gained == 0
        engine.flush(uids)
    assert engine.compiles == engine_programs


def test_serving_monitor_write_emits_the_names(serving):
    engine, gained, _ = serving
    with tracer.stage("warmup"):
        with tracer.stage("passes"):
            pass
    sink = _Sink()
    engine.write_monitor_events(sink, step=5)
    names = {n for n, _, s in sink.events if s == 5}
    assert {"setup/warmup_s", "setup/warmup/passes_s"} <= names


def _train_engine(monkeypatch, limit, **kw):
    import deepspeed_tpu
    from deepspeed_tpu.accelerator import get_accelerator
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    monkeypatch.setattr(type(get_accelerator()), "total_memory",
                        lambda self, device_index=None: limit)
    model = LlamaForCausalLM(LlamaConfig.tiny(remat=True, vocab_size=128))
    engine, *_ = deepspeed_tpu.initialize(
        model=model, rngs=jax.random.PRNGKey(0),
        config={"train_batch_size": 8, "steps_per_print": 0,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3}, "mesh": {"fsdp": 8}}, **kw)
    batch = {"input_ids": np.arange(8 * 64, dtype=np.int32).reshape(8, 64)
             % 128}
    return engine, batch


@pytest.mark.parametrize("limit, rungs", [(0, []), (1 << 30, ["rung0"])])
def test_train_engine_leaves_its_stages(monkeypatch, limit, rungs):
    """No limit (the CPU): the plan is looked for and the step compiles in
    ``first_step``. With one: the ladder compiles it in ``remat_fit``."""
    compiled = _totals("compile/")
    engine, batch = _train_engine(monkeypatch, limit)
    assert set(_totals("setup/")) == {"setup/engine_init_s"}
    assert tracer.phase() == "before"
    row = compile_cache.programs().get("step_fn", {}).get("warmup", [0] * 4)
    loss = engine.train_batch(batch)
    setup = _totals("setup/")
    assert set(setup) == {
        "setup/engine_init_s", "setup/state_build_s", "setup/remat_fit_s",
        "setup/remat_fit/plan_s", "setup/first_step_s"} | {
        f"setup/remat_fit/{r}_s" for r in rungs}
    assert tracer.phase() == "traffic" and np.isfinite(float(loss))
    step = sum(compile_cache.programs()["step_fn"]["warmup"][1:]) \
        - sum(row[1:])
    held = setup["setup/remat_fit_s" if rungs else "setup/first_step_s"]
    assert 0 < step <= held
    build = sum(_gained(compiled, "compile/build/").get(
        f"compile/build/{k}", 0.0) for k in KINDS[:3])
    assert 0 < build <= setup["setup/state_build_s"]
    # a later step opens no stage and builds no program (the second may
    # look its trace up again: the state it is handed is now a step's output)
    engine.train_batch(batch)
    before = dict(tracer.totals)
    engine.train_batch(batch)
    assert {k: v for k, v in tracer.totals.items()
            if before.get(k) != v} == {}
    engine.destroy()


def test_train_engine_given_parameters_builds_its_state_inside_engine_init(
        monkeypatch):
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    model = LlamaForCausalLM(LlamaConfig.tiny(vocab_size=128))
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": jnp.zeros((8, 8), jnp.int32)})["params"]
    t0 = time.perf_counter()
    engine, _ = _train_engine(monkeypatch, 0, model_parameters=params)
    wall = time.perf_counter() - t0  # jaxlint: disable=JL001 -- an upper bound on the stages inside, which block
    setup = _totals("setup/")
    assert set(setup) == {"setup/engine_init_s", "setup/state_build_s"}
    assert 0 < setup["setup/state_build_s"] and 0 < setup["setup/engine_init_s"]
    assert sum(setup.values()) <= wall          # engine_init is net of it
    engine.destroy()


def test_train_monitor_write_emits_the_names(monkeypatch):
    engine, batch = _train_engine(monkeypatch, 0)
    sink = _Sink()
    sink.enabled = True
    engine.monitor = sink
    engine.config.steps_per_print = 1
    engine.train_batch(batch)
    engine.drain_metrics()
    names = {n for n, _, _ in sink.events}
    assert {"setup/engine_init_s", "setup/state_build_s",
            "setup/first_step_s", "compile/warmup/backend_s",
            "compile/warmup/programs"} <= names
    engine.destroy()
