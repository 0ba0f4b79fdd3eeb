"""Fault-tolerant serving (inference/v2/serving/health.py): replica failure
detection (liveness + progress-stall deadlines), request failover with KV
salvage, self-healing rejoin, the prefix-index listener lifecycle, and the
bounded-retry disaggregated handoff. docs/SERVING.md "Failure semantics"
describes the design under test."""

import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.serving import (ServingCluster, ServingRouter)
from deepspeed_tpu.inference.v2.serving.health import (DOWN, DRAINING,
                                                       HEALTHY, SUSPECT,
                                                       HealthMonitor)
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.monitor.serving import HealthStats
from deepspeed_tpu.utils import fault_injection as fi
from deepspeed_tpu.utils.resilience import IOTimeout

_CLASSES = [{"name": "hi", "priority": 2,
             "ttft_slo_ms": 1e6, "tbt_slo_ms": 1e6},
            {"name": "lo", "priority": 0,
             "ttft_slo_ms": 1e6, "tbt_slo_ms": 1e6}]
_SERVING = {"decode_slice": 4, "idle_wait_s": 0.005, "classes": _CLASSES}
#: fast deadlines so stall detection fits a unit test (still generous
#: enough that a GIL-contended warm step on a 2-core box stays under them)
_HEALTH = {"enabled": True, "interval_s": 0.01,
           "suspect_after_s": 0.25, "down_after_s": 0.6,
           "fence_join_s": 0.5}


def _model_and_params(seed=0):
    cfg = LlamaConfig.tiny(vocab_size=128, max_position_embeddings=256)
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(seed),
                        {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]
    return model, params


@pytest.fixture(scope="module")
def model_params():
    return _model_and_params()


def _build_engine(model_params, num_blocks=24, prefix_cache=False,
                  preemption=None, warmup=False):
    model, params = model_params
    serving = dict(_SERVING)
    if preemption is not None:
        serving["preemption"] = preemption
    econf = {"dtype": jnp.float32,
             "state_manager": {"max_tracked_sequences": 8,
                               "max_ragged_sequence_count": 4,
                               "max_ragged_batch_size": 96,
                               "max_context": 176,
                               "prefill_chunk_size": 32},
             "kv_cache": {"block_size": 16, "num_blocks": num_blocks},
             "serving": serving}
    if prefix_cache:
        econf["prefix_cache"] = {"enabled": True}
    if warmup:
        econf["compile"] = {"warmup": True}
    return InferenceEngineV2(model=model, model_parameters=params,
                             config=econf)


def _force_paged(engine):
    """Hold the kernel path constant: a
    migration re-prefill is a from-zero prefill, which would take the
    PACKED fast path while the uninterrupted reference decoded through the
    paged kernels — the two carry a benign per-path numeric variance that
    would make a byte-equality gate flaky. Forced-paged, the chunk kernel
    is bit-equal to the decode kernels (established in PR 9), so the gate
    tests exactly what failover changes: WHERE the stream runs."""
    orig = engine.scheduler.schedule_pass

    def no_fast_path():
        b = orig()
        if b is not None:
            b.pure_prefill = False
        return b

    engine.scheduler.schedule_pass = no_fast_path


def _warm(rt, rng, n=1):
    """Serve one tiny request on EVERY replica frontend BEFORE the router
    (and its health monitor) starts: a cold engine's first pass compiles
    for ~seconds, which the aggressive unit-test stall deadlines would
    misread as a wedged replica. Call before ``rt.start()``."""
    rt.cluster.start()
    for r in rt.cluster.frontends:
        for _ in range(n):
            h = r.frontend.submit(_prompt(rng, 8), priority="lo",
                                  max_new_tokens=2)
            assert r.frontend.drain(timeout=120)
            assert h.status == "finished"


def _rng():
    return np.random.RandomState(0)


def _prompt(rng, n):
    return rng.randint(0, 128, size=(n,)).astype(np.int32)


def _direct_stream(engine, prompt, n):
    uid = 97_000 + _direct_stream.k
    _direct_stream.k += 1
    engine._put_nofetch([uid], [np.asarray(prompt, np.int32)])
    out = engine.decode_pipeline([uid]).run(n)
    engine.flush([uid])
    return [int(t) for t in out[0]]


_direct_stream.k = 0


def _router(engines, health=None, router_cfg=None, roles=None):
    cluster = ServingCluster(engines, serving=_SERVING, roles=roles)
    cfg = dict(router_cfg or {"policy": "round_robin"})
    cfg["health"] = dict(_HEALTH if health is None else health)
    return ServingCluster, ServingRouter(cluster, cfg)


def _crash(replica):
    """Kill a replica's serving loop the way the PR 10 crash test does."""
    boom = RuntimeError("injected crash")

    def bad(*a, **k):
        raise boom

    replica.engine._run_pass = bad
    replica.frontend._pipe.run = bad


def _uncrash(replica):
    try:
        del replica.engine._run_pass
    except AttributeError:
        pass


# --------------------------------------------------------------------------- #
# crash failover: detection, migration, byte-identical resumption
# --------------------------------------------------------------------------- #

def test_crash_failover_stream_byte_identical(model_params):
    """An engine-thread crash mid-stream: the health monitor detects it,
    fences the corpse, migrates the request, and the SAME handle's stream
    completes byte-identical to an uninterrupted run — no raise at drain,
    a one-time ``migrated`` marker, and the dead replica out of rotation."""
    e0, e1 = _build_engine(model_params), _build_engine(model_params)
    _force_paged(e0)
    _force_paged(e1)
    rng = _rng()
    p = _prompt(rng, 24)
    ref = _direct_stream(e0, p, 60)
    _, rt = _router([e0, e1],
                    health=dict(_HEALTH, auto_rejoin=False))
    _warm(rt, rng)
    rt.start()
    h = rt.submit(p, priority="hi", max_new_tokens=60)      # rr -> r0
    for _t in h:                     # stream flowing on r0
        break
    _crash(rt.cluster.replica("r0"))
    assert rt.drain(timeout=60)      # handled: drain does NOT raise
    assert h.result(timeout=10) == ref
    assert h.status == "finished"
    assert h.migrated == 1
    st = rt.health.stats
    assert st.liveness_downs == 1
    assert st.migrations == 1 and st.reprefilled == 1
    assert rt.health.state("r0") == DRAINING     # out of rotation, no rejoin
    assert rt.health.state("r1") == HEALTHY
    # new traffic lands on the survivor only
    h2 = rt.submit(p, priority="hi", max_new_tokens=4)
    assert rt.drain(timeout=60)
    assert h2.status == "finished"
    _uncrash(rt.cluster.replica("r0"))
    rt.close()                       # handled failure: close does not raise
    rt.close()


def test_stall_detection_and_migration(model_params):
    """A WEDGED replica (loop thread alive but frozen) walks
    healthy -> suspect -> down on the progress heartbeat's stall deadline;
    its stream migrates and completes byte-identically, and the woken
    thread's late emissions are dropped by the fence/seal (no duplicate or
    divergent tokens)."""
    e0, e1 = _build_engine(model_params), _build_engine(model_params)
    _force_paged(e0)
    _force_paged(e1)
    rng = _rng()
    p = _prompt(rng, 24)
    ref = _direct_stream(e0, p, 48)
    _, rt = _router([e0, e1], health=dict(_HEALTH, auto_rejoin=False))
    _warm(rt, rng)
    rt.start()
    h = rt.submit(p, priority="hi", max_new_tokens=48)      # rr -> r0
    for _t in h:
        break
    # wedge r0's loop: the next step() blocks until released (well past the
    # down deadline) — liveness stays OK, progress freezes
    gate = threading.Event()
    fe0 = rt.cluster.replica("r0").frontend
    orig_step = fe0.step

    def wedged_step():
        gate.wait(5.0)
        return orig_step()

    fe0.step = wedged_step
    saw_suspect = False
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        rt.health.poll()
        s = rt.health.state("r0")
        saw_suspect = saw_suspect or s == SUSPECT
        # DOWN is passed through: when the router's own monitor thread wins
        # the race to detect the stall, this thread sees DOWN while that one
        # still fences and joins, before it marks the replica DRAINING
        if s == DRAINING:
            break
        time.sleep(0.01)
    assert rt.health.state("r0") == DRAINING
    assert saw_suspect                   # passed through suspect first
    assert rt.health.stats.stall_downs >= 1
    assert rt.health.stats.detect_ms        # latency recorded
    gate.set()                           # the wedged thread wakes fenced
    assert rt.drain(timeout=60)
    assert h.result(timeout=10) == ref   # exact: no duplicates, no gaps
    assert h.migrated == 1
    rt.close()


def test_fenced_frontend_emits_nothing(model_params):
    """Unit contract behind the stall case: a fenced frontend's
    ``_on_tokens`` drops the row and stops every uid; a sealed handle's
    row is dropped for that request alone."""
    e = _build_engine(model_params)
    fe = e.serving_frontend()
    req = fe.submit(np.arange(4, dtype=np.int32), priority="hi",
                    max_new_tokens=8)
    fe._drain_control()
    req.status = "decoding"
    fe._live[req.uid] = req
    # sealed: row dropped for this request
    req._seal()
    assert fe._on_tokens(0, [req.uid], np.asarray([[7]])) is None
    assert req.tokens == [] and req._q.qsize() == 0
    with req._emit_lock:
        req._sealed = False
    # fenced: everything stops, nothing lands
    fe.fence()
    assert fe._on_tokens(0, [req.uid], np.asarray([[7]])) == [req.uid]
    assert req.tokens == [] and req._q.qsize() == 0


# --------------------------------------------------------------------------- #
# KV salvage: preempt-offloaded pages become a survivor's import
# --------------------------------------------------------------------------- #

def test_offloaded_kv_salvaged_through_import(model_params):
    """A victim preempted-by-offload whose WHOLE KV sits in pinned host
    buffers when its replica dies is salvaged: the buffers ride
    ``submit_handoff`` -> ``import_kv`` on a survivor (zero recompute) and
    the stream completes byte-identically."""
    e0 = _build_engine(model_params, num_blocks=14)
    e1 = _build_engine(model_params, num_blocks=24)
    rng = _rng()
    p_lo = [_prompt(rng, 24), _prompt(rng, 24)]
    refs = [_direct_stream(e1, p, 48) for p in p_lo]
    _, rt = _router([e0, e1], health=dict(_HEALTH, auto_rejoin=False))
    # drive r0's loop synchronously (no thread): deterministic preemption
    fe0 = rt.cluster.replica("r0").frontend
    lows = [fe0.submit(p, priority="lo", max_new_tokens=48) for p in p_lo]
    for _ in range(60):                    # decode until pool pressure
        fe0.step()
        if e0.scheduler.available_blocks < 8:
            break
    h_hi = fe0.submit(_prompt(rng, 96), priority="hi", max_new_tokens=4)
    for _ in range(200):
        fe0.step()
        if fe0.offload._recs:
            break
    assert fe0.offload._recs               # a victim parked in host buffers
    victim_uid = next(iter(fe0.offload._recs))
    victim = next(h for h in lows if h.uid == victim_uid)
    ref = refs[lows.index(victim)]
    assert fe0.offload.salvageable(victim_uid)
    n_before = len(victim.tokens)
    assert 0 < n_before < 48
    # r0 dies with the victim still offloaded
    fe0._loop_exc = RuntimeError("injected death")
    rt.cluster.replica("r1").frontend.start()
    rt.health.poll()                       # detect + failover synchronously
    st = rt.health.stats
    assert st.salvaged == 1 and st.salvaged_bytes > 0
    assert st.salvaged_tokens == len(victim.prompt) + n_before
    assert rt.cluster.replica("r1").frontend.drain(timeout=120)
    assert victim.result(timeout=10) == ref  # byte-identical across salvage
    assert victim.migrated == 1
    # the other requests were decoding (not offloaded): re-prefilled on the
    # survivor (or already finished at the crash)
    assert h_hi.status == "finished" and len(h_hi.tokens) == 4
    assert all(h.status == "finished" for h in lows)
    assert st.reprefilled >= 1
    rt.close()


# --------------------------------------------------------------------------- #
# cancel-during-migration + double failure
# --------------------------------------------------------------------------- #

def test_cancel_during_migration_releases_everything(model_params):
    """``h.cancel()`` landing while a request is mid-failover: the handle
    terminal-states (no hang), and after the failed replica rejoins, every
    replica the request touched is back at allocator baseline."""
    e0, e1 = _build_engine(model_params), _build_engine(model_params)
    _force_paged(e0)
    _force_paged(e1)
    free0, free1 = e0.free_blocks, e1.free_blocks
    rng = _rng()
    _, rt = _router([e0, e1], health=dict(_HEALTH, auto_rejoin=False))
    _warm(rt, rng)
    rt.start()
    h = rt.submit(_prompt(rng, 24), priority="hi", max_new_tokens=48)
    for _t in h:
        break
    _crash(rt.cluster.replica("r0"))
    h.cancel()                       # lands in the failover window
    assert rt.drain(timeout=60)
    assert h.result(timeout=10) is not None
    assert h.status in ("cancelled", "finished")
    _uncrash(rt.cluster.replica("r0"))
    assert rt.rejoin("r0")           # reset reclaims the dead state
    assert rt.health.state("r0") == HEALTHY
    rt.close()
    assert e0.free_blocks == free0
    assert e1.free_blocks == free1


def test_double_failure_completes_on_third_or_sheds(model_params):
    """A second replica dying during migration: with a third survivor the
    stream completes there (byte-identical); with none left it sheds
    cleanly — closed stream, no hang, no leaked pages."""
    engines = [_build_engine(model_params) for _ in range(3)]
    for e in engines:
        _force_paged(e)
    frees = [e.free_blocks for e in engines]
    rng = _rng()
    p = _prompt(rng, 24)
    ref = _direct_stream(engines[0], p, 40)
    _, rt = _router(engines, health=dict(_HEALTH, auto_rejoin=False))
    _warm(rt, rng)
    rt.start()
    h = rt.submit(p, priority="hi", max_new_tokens=40)      # rr -> r0
    for _t in h:
        break
    # r1 dies FIRST (so migration off r0 must skip it), then r0 dies
    _crash(rt.cluster.replica("r1"))
    _crash(rt.cluster.replica("r0"))
    assert rt.drain(timeout=60)
    assert h.result(timeout=10) == ref   # completed on r2
    # one hop if failover skipped the already-dead r1, two if the request
    # landed on r1 before ITS death was detected — either way it completed
    assert h.status == "finished" and h.migrated in (1, 2)
    for r in ("r0", "r1"):
        _uncrash(rt.cluster.replica(r))
        assert rt.rejoin(r)
    rt.close()
    for e, f in zip(engines, frees):
        assert e.free_blocks == f

    # --- no survivor at all: clean shed ------------------------------- #
    e0, e1 = _build_engine(model_params), _build_engine(model_params)
    _, rt = _router([e0, e1], health=dict(_HEALTH, auto_rejoin=False))
    _warm(rt, rng)
    rt.start()
    h = rt.submit(_prompt(rng, 24), priority="hi", max_new_tokens=40)
    for _t in h:
        break
    _crash(rt.cluster.replica("r1"))
    _crash(rt.cluster.replica("r0"))
    assert rt.drain(timeout=60)
    assert h.result(timeout=10) is not None     # stream closed, not hung
    assert h.status == "shed"
    assert rt.health.stats.migration_sheds >= 1
    # the whole cluster is down: a new submit sheds at the router
    h2 = rt.submit(_prompt(rng, 8), priority="hi", max_new_tokens=4)
    assert h2.status == "shed"
    rt.close()


# --------------------------------------------------------------------------- #
# satellite: prefix-index listener lifecycle (evict on close AND on failure)
# --------------------------------------------------------------------------- #

def test_closed_replica_index_evicted_and_unroutable(model_params):
    """Regression (PR 10 gap): a replica frontend closed out of band used
    to keep its chain->holders entries forever and keep attracting
    cache-affine routes. Now close evicts its index entries and routing
    skips it — a same-prefix request lands on the survivor and completes."""
    e0 = _build_engine(model_params, prefix_cache=True)
    e1 = _build_engine(model_params, prefix_cache=True)
    rng = _rng()
    prefix = _prompt(rng, 32)

    def with_prefix(tail):
        return np.concatenate([prefix, _prompt(rng, tail)])

    # health DISABLED: the close-listener path must work on its own
    cluster = ServingCluster([e0, e1], serving=_SERVING)
    rt = ServingRouter(cluster, {"policy": "cache_aware", "balance": 1e-9})
    rt.start()
    p0 = with_prefix(8)
    h = rt.submit(p0, priority="hi", max_new_tokens=4)
    assert rt.drain(timeout=60)
    warm = max(rt.stats.routed, key=lambda k: rt.stats.routed[k])
    assert rt.index.holders(warm) > 0
    # close the warm replica's frontend OUT OF BAND
    rt.cluster.replica(warm).frontend.close()
    assert rt.index.holders(warm) == 0          # entries evicted at close
    h2 = rt.submit(with_prefix(8), priority="hi", max_new_tokens=4)
    assert rt.drain(timeout=60)
    assert h2.status == "finished"              # routed to the survivor
    other = "r1" if warm == "r0" else "r0"
    assert rt.stats.routed[other] >= 1
    rt.close()
    assert h.status == "finished"


def test_failed_replica_index_evicted(model_params):
    """Detected failure evicts the dead replica's chain entries too."""
    e0 = _build_engine(model_params, prefix_cache=True)
    e1 = _build_engine(model_params, prefix_cache=True)
    rng = _rng()
    _, rt = _router([e0, e1], health=dict(_HEALTH, auto_rejoin=False),
                    router_cfg={"policy": "cache_aware"})
    _warm(rt, rng)
    rt.start()
    p = _prompt(rng, 32)
    h = rt.submit(p, priority="hi", max_new_tokens=4)
    assert rt.drain(timeout=60)
    warm = max(rt.stats.routed, key=lambda k: rt.stats.routed[k])
    assert rt.index.holders(warm) > 0
    _crash(rt.cluster.replica(warm))
    # an idle crashed loop only dies when it next works: send traffic (the
    # warm prefix steers it onto the corpse) and let detection migrate it
    h2 = rt.submit(p, priority="hi", max_new_tokens=4)
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline and rt.index.holders(warm):
        rt.health.poll()
        time.sleep(0.01)
    assert rt.index.holders(warm) == 0
    assert rt.drain(timeout=60)
    _uncrash(rt.cluster.replica(warm))
    rt.close()
    assert h.status == "finished"
    assert h2.status == "finished"


# --------------------------------------------------------------------------- #
# self-healing: rejoin resets, re-warms, re-registers
# --------------------------------------------------------------------------- #

def test_rejoin_fresh_uid_space_zero_new_programs(model_params):
    """Rejoin rebuilds the frontend in a FRESH uid space, re-warms with
    ZERO new programs on an already-warm engine, replays the surviving
    radix tree into the index, and the replica serves again."""
    e0 = _build_engine(model_params, prefix_cache=True, warmup=True)
    e1 = _build_engine(model_params, prefix_cache=True, warmup=True)
    rng = _rng()
    p = _prompt(rng, 32)
    _, rt = _router([e0, e1], health=dict(_HEALTH, auto_rejoin=False),
                    router_cfg={"policy": "cache_aware"})
    # warm BOTH replicas' caches through real traffic BEFORE the monitor
    # starts (the first COW adoption compiles a page-copy program, which
    # the aggressive test deadlines would misread as a stall)
    rt.cluster.start()
    for _ in range(2):
        for repl in ("r0", "r1"):
            fe = rt.cluster.replica(repl).frontend
            hh = fe.submit(p, priority="hi", max_new_tokens=4)
            assert fe.drain(timeout=120)
            assert hh.status == "finished"
    rt.start()
    fe0_old = rt.cluster.replica("r0").frontend
    old_base = next(fe0_old._uid_iter)
    # an idle loop never trips over a poisoned pass — declare the death
    # directly (the loop-exc liveness signal) and let one poll handle it
    fe0_old._loop_exc = RuntimeError("injected death")
    rt.health.poll()
    assert rt.health.state("r0") == DRAINING
    c0 = e0.compiles
    assert rt.rejoin("r0")
    assert e0.compiles - c0 == 0        # re-warm compiled nothing new
    assert rt.health.stats.rejoins == 1
    fe0 = rt.cluster.replica("r0").frontend
    assert fe0 is not fe0_old
    new_base = next(fe0._uid_iter)
    assert new_base > old_base          # fresh, disjoint uid space
    assert (new_base >> 24) != (old_base >> 24)
    # the surviving radix tree replayed into the index
    assert rt.index.holders("r0") > 0
    h = rt.submit(p, priority="hi", max_new_tokens=4)
    assert rt.drain(timeout=60)
    assert h.status == "finished"
    rt.close()


# --------------------------------------------------------------------------- #
# satellite: disaggregated handoff under retry_call/IOTimeout
# --------------------------------------------------------------------------- #

def test_handoff_retry_then_success(model_params):
    """A transient handoff failure (one injected raise) retries within the
    budget and the stream completes normally."""
    e_pre, e_dec = _build_engine(model_params), _build_engine(model_params)
    fi.install(fi.parse_plan("serve.handoff:at=1:action=raise"))
    try:
        cluster = ServingCluster([e_pre, e_dec],
                                 roles=["prefill", "decode"],
                                 serving=_SERVING)
        rt = ServingRouter(cluster, {"topology": "disaggregated",
                                     "handoff_retries": 3,
                                     "handoff_backoff_s": 0.01}).start()
        h = rt.submit(_prompt(_rng(), 24), priority="hi", max_new_tokens=4)
        assert rt.drain(timeout=60)
        assert h.status == "finished" and len(h.tokens) == 4
        assert rt.stats.handoffs == 1
        assert rt.stats.handoff_failures == 0
        rt.close()
    finally:
        fi.clear()


def test_handoff_budget_exhausted_surfaces_named(model_params):
    """Every attempt failing (injected) exhausts the bounded budget: the
    request sheds with the error NAMED on the handle — ``result()``
    re-raises it, naming the prefill replica — never a silent hang."""
    e_pre, e_dec = _build_engine(model_params), _build_engine(model_params)
    fi.install(fi.parse_plan("serve.handoff:every=1:action=raise"))
    try:
        cluster = ServingCluster([e_pre, e_dec],
                                 roles=["prefill", "decode"],
                                 serving=_SERVING)
        rt = ServingRouter(cluster, {"topology": "disaggregated",
                                     "handoff_retries": 2,
                                     "handoff_backoff_s": 0.01}).start()
        h = rt.submit(_prompt(_rng(), 24), priority="hi", max_new_tokens=4)
        assert rt.drain(timeout=60)
        assert h.status == "shed"
        with pytest.raises(RuntimeError, match="prefill replica 'r0'"):
            h.result(timeout=5)
        assert rt.stats.handoff_failures == 1
        rt.close()
    finally:
        fi.clear()


def test_handoff_stall_times_out(model_params):
    """A STALLED handoff attempt (injected sleep past handoff_timeout_s)
    surfaces IOTimeout inside the retry loop instead of wedging the prefill
    worker; with only one decode replica the budget exhausts and the error
    chain names the timeout."""
    e_pre, e_dec = _build_engine(model_params), _build_engine(model_params)
    fi.install(fi.parse_plan(
        "serve.handoff:every=1:action=stall:delay_s=0.5"))
    try:
        cluster = ServingCluster([e_pre, e_dec],
                                 roles=["prefill", "decode"],
                                 serving=_SERVING)
        rt = ServingRouter(cluster, {"topology": "disaggregated",
                                     "handoff_retries": 2,
                                     "handoff_timeout_s": 0.05,
                                     "handoff_backoff_s": 0.01}).start()
        h = rt.submit(_prompt(_rng(), 24), priority="hi", max_new_tokens=4)
        assert rt.drain(timeout=60)
        assert h.status == "shed"
        assert h.error is not None
        assert isinstance(h.error.__cause__, IOTimeout)
        rt.close()
    finally:
        fi.clear()


# --------------------------------------------------------------------------- #
# fault-injection sites exist where the chaos bench aims
# --------------------------------------------------------------------------- #

def test_serving_fault_sites_fire(model_params):
    """The serving chaos sites are actually threaded through the code:
    serve.engine_step.<replica> crashes exactly the targeted loop;
    serve.kv_fetch raises out of the page gather."""
    e0 = _build_engine(model_params)
    fi.install(fi.parse_plan("serve.kv_fetch:at=1:action=raise"))
    try:
        e0._put_nofetch([5], [_prompt(_rng(), 20)])
        with pytest.raises(fi.InjectedFault):
            e0.fetch_pages(list(e0.scheduler.seqs[5].blocks))
        e0.flush([5])
    finally:
        fi.clear()

    e1 = _build_engine(model_params)
    fi.install(fi.parse_plan("serve.engine_step.r0:at=2:action=raise"))
    try:
        # huge stall deadlines: these engines run COLD (warming would
        # advance r0's step counter past the at=2 trigger), and a cold
        # migration re-prefill compiles — only the liveness path is under
        # test here
        _, rt = _router([e0, e1],
                        health=dict(_HEALTH, auto_rejoin=False,
                                    suspect_after_s=10.0,
                                    down_after_s=30.0))
        rt.start()
        h = rt.submit(_prompt(_rng(), 16), priority="hi", max_new_tokens=8)
        assert rt.drain(timeout=60)
        # r0's loop died on its 2nd step; the stream still finished
        assert h.status == "finished" and len(h.tokens) == 8
        assert rt.health.stats.liveness_downs == 1
        assert rt.health.state("r0") == DRAINING
        rt.close()
    finally:
        fi.clear()


# --------------------------------------------------------------------------- #
# observability: HealthStats events + serve/health spans through trace_check
# --------------------------------------------------------------------------- #

def test_health_stats_events_shape():
    st = HealthStats(["r0", "r1"])
    st.record_transition("r0", "healthy", "suspect")
    st.record_transition("r0", "suspect", "down")
    st.record_detection("stall", 0.4)
    st.record_migration("salvage", 48, 4096)
    st.record_migration("reprefill", 30)
    st.record_rejoin(0.25)
    ev = {name: v for name, v, _ in st.events(step=3)}
    assert ev["serve/health/transitions"] == 2.0
    assert ev["serve/health/stall_downs"] == 1.0
    assert ev["serve/health/migrations"] == 2.0
    assert ev["serve/health/salvaged"] == 1.0
    assert ev["serve/health/salvaged_tokens"] == 48.0
    assert ev["serve/health/salvaged_bytes"] == 4096.0
    assert ev["serve/health/reprefilled_tokens"] == 30.0
    assert ev["serve/health/rejoins"] == 1.0
    assert ev["serve/health/rejoin_warmup_ms"] == pytest.approx(250.0)
    assert ev["serve/health/detect_p50_ms"] == pytest.approx(400.0)
    assert ev["serve/health/state/r0"] == 2.0       # down
    assert ev["serve/health/state/r1"] == 0.0       # healthy


def test_health_spans_pass_trace_check(model_params, tmp_path):
    """Detection, migration and rejoin leave serve/health spans — from the
    same perf stamps the stats aggregate — that pass the real trace_check
    with a required serve/health track."""
    from deepspeed_tpu.monitor.trace import tracer
    tracer.reset()
    tracer.configure(trace_dir=str(tmp_path), enabled=True)
    try:
        e0, e1 = _build_engine(model_params), _build_engine(model_params)
        rng = _rng()
        _, rt = _router([e0, e1], health=dict(_HEALTH, auto_rejoin=False))
        _warm(rt, rng)
        rt.start()
        h = rt.submit(_prompt(rng, 24), priority="hi", max_new_tokens=24)
        for _t in h:
            break
        _crash(rt.cluster.replica("r0"))
        assert rt.drain(timeout=60)
        _uncrash(rt.cluster.replica("r0"))
        assert rt.rejoin("r0")
        rt.close()
        assert h.status == "finished"
        names = tracer.summary()
        assert "serve/health/detect" in names
        assert "serve/health/migrate" in names
        assert "serve/health/rejoin" in names
        # stats-equals-spans: one detect per down, one migrate per
        # migration, one rejoin per rejoin
        st = rt.health.stats
        assert names["serve/health/detect"][0] == \
            st.liveness_downs + st.stall_downs
        assert names["serve/health/migrate"][0] == st.migrations
        assert names["serve/health/rejoin"][0] == st.rejoins
        _trace_check(tracer.export(), "--require", "serve/health")
    finally:
        tracer.reset()


def test_cluster_uid_spaces_disjoint(model_params):
    """Cluster frontends mint uids from disjoint spaces — migration can
    move any handle anywhere without collision."""
    e0, e1 = _build_engine(model_params), _build_engine(model_params)
    cluster = ServingCluster([e0, e1], serving=_SERVING)
    b0 = next(cluster.replicas[0].frontend._uid_iter)
    b1 = next(cluster.replicas[1].frontend._uid_iter)
    assert (b0 >> 24) != (b1 >> 24)
    assert cluster.alloc_uid_base() > max(b0, b1)


def test_monitor_reads_never_wait_out_a_blocking_failover():
    """Regression (threadlint TL002): ``poll()`` used to hold the monitor
    lock through the whole failover — including ``fe.join(fence_join_s)``
    on the dead replica's thread — so ``all_healthy()`` /
    ``handled_replicas()`` from the router or a bench waited out the full
    fence-join timeout behind it. The restructure CLAIMS the record under
    the lock and runs the blocking legs with the lock released; this test
    parks a fake frontend inside the fence join and asserts the read
    surface still answers immediately."""
    entered, release = threading.Event(), threading.Event()

    class _FE:
        _loop_exc = RuntimeError("engine loop died")   # liveness -> down
        _reqs: dict = {}
        _inflight_lock = threading.Lock()

        def fence(self):
            pass

        def join(self, timeout):
            entered.set()
            release.wait(timeout)   # honors fence_join_s: pre-fix the
            # monitor lock stayed held for this whole wait

        def _scrape_control(self):
            return []

    class _Replica:
        name, role = "r0", "decode"
        frontend, engine = _FE(), None

    class _Cluster:
        replicas = [_Replica()]

    class _Router:
        cluster = _Cluster()
        dropped: list = []

        def _drop_replica_routing(self, name):
            self.dropped.append(name)

    mon = HealthMonitor(_Router(), {
        "enabled": True, "interval_s": 0.01, "suspect_after_s": 0.25,
        "down_after_s": 0.6, "fence_join_s": 2.0, "auto_rejoin": False})
    t = threading.Thread(target=mon.poll, daemon=True)
    t.start()
    assert entered.wait(2.0), "failover never reached the fence join"
    try:
        # the blocking leg is in flight RIGHT NOW; reads must not queue
        # behind it (pre-fix: these blocked ~fence_join_s = 2 s)
        t0 = time.perf_counter()
        assert mon.all_healthy() is False
        assert mon.state("r0") == DOWN
        assert mon.handled_replicas() == []   # claimed, not yet handled
        assert time.perf_counter() - t0 < 0.5
    finally:
        release.set()
        t.join(5.0)
    assert not t.is_alive()
    assert mon.state("r0") == DRAINING
    assert mon.handled_replicas() == ["r0"]


# --------------------------------------------------------------------------- #
# whole cluster scenarios, every platform-independent gate at once, with the
# lock-order sanitizer armed and cross-checked against threadlint's graph
# --------------------------------------------------------------------------- #

def _trace_check(path, *args):
    """Run the real ``scripts/trace_check.py`` over ``path``."""
    import pathlib
    import subprocess
    import sys
    r = subprocess.run(
        [sys.executable, "scripts/trace_check.py", str(path), *args],
        capture_output=True, text=True,
        cwd=str(pathlib.Path(__file__).resolve().parents[2]))
    assert r.returncode == 0, r.stdout + r.stderr


@functools.lru_cache(maxsize=None)
def _static_lock_edges():
    """threadlint's static lock graph over the package, under the repo's
    own ``.threadlint.json`` (found from the package upward;
    docs/THREADLINT.md)."""
    import pathlib
    from deepspeed_tpu.tools.threadlint.model import static_lock_graph
    root = pathlib.Path(__file__).resolve().parents[2]
    return frozenset(static_lock_graph([str(root / "deepspeed_tpu")]))


def _assert_ledgers_tile(handles):
    """Every finished request's phase stints sum to the latency its client
    measured (TTFT + sum of gaps), within the tolerance ``serve/slo/
    attr_consistent`` itself applies — across handoffs and migrations."""
    from deepspeed_tpu.inference.v2.serving.frontend import \
        attribution_epsilon
    checked = 0
    for h in handles:
        attr = h.attribution()
        if h.status != "finished" or attr["client_s"] is None:
            continue
        checked += 1
        assert abs(attr["total_s"] - attr["client_s"]) \
            <= attribution_epsilon(attr["client_s"]), (h.uid, h.migrated,
                                                       attr)
    assert checked


def _assert_migrated_chains(handles, tracer):
    """A migrated request keeps one story: a ``migration`` stint on its
    ledger, and spans sharing its trace_id on at least two lanes, one of
    them the health lane's migrate span."""
    lanes = {}
    for kind, name, _t0, _t1, lane, args in tracer.iter_records():
        if kind == "X" and args and "trace_id" in args:
            lanes.setdefault(args["trace_id"], set()).add((lane, name))
    migrated = [h for h in handles if h.status == "finished" and h.migrated]
    assert migrated
    for h in migrated:
        assert any(p == "migration" for p, _, _ in h.timeline()), h.uid
        recs = lanes.get(h.trace_id, set())
        assert len({lane for lane, _ in recs}) >= 2, (h.uid, recs)
        assert any(n == "serve/health/migrate" for _, n in recs), h.uid


def _stream_until_first_token(handles):
    for h in handles:
        for _t in h:
            break


@pytest.mark.parametrize("scenario", ["failover_rejoin",
                                      "disaggregated_handoff",
                                      "cache_aware_routing"])
def test_cluster_scenario_under_lock_sanitizer(model_params, tmp_path,
                                               scenario):
    """The three cluster scenarios, whole, on warmed replicas with every
    lock a recording proxy and request flow tracing on. What must hold on
    any platform: every stream byte-identical to an uninterrupted direct
    run (kernel path held constant), zero compiles on every replica —
    a rejoin's re-warm included —, every allocator back at its baseline,
    every finished request's ledger summing to its client's latency, no
    cycle in the observed lock order and no observed edge that threadlint's
    static graph does not predict.

    - ``failover_rejoin``: one replica's loop is killed by an injected
      raise and, once it has healed, the other's is stalled past the down
      deadline; both are detected, their streams migrate, both rejoin on
      their own and end healthy; the raise leaves the flight-recorder dump
      and every migrated request keeps one flow chain across lanes.
    - ``disaggregated_handoff``: a prefill and a decode replica; every
      request's KV crosses the page fabric once.
    - ``cache_aware_routing``: two serving replicas with radix caches
      behind the cache-aware policy; requests sharing a prefix follow it.
    """
    from deepspeed_tpu.monitor.trace import tracer
    from deepspeed_tpu.utils import locksan
    locksan.arm()                    # before any lock below is built
    tracer.reset()
    tracer.configure(trace_dir=str(tmp_path), enabled=True)
    rt = None
    try:
        cache = scenario == "cache_aware_routing"
        engines = [_build_engine(model_params, prefix_cache=cache,
                                 warmup=True) for _ in range(2)]
        for e in engines:
            _force_paged(e)
        rng = _rng()
        if cache:
            shared = [_prompt(rng, 48), _prompt(rng, 48)]
            prompts = [np.concatenate([shared[i % 2], _prompt(rng, 6)])
                       for i in range(8)]
        else:
            prompts = [_prompt(rng, n) for n in (24, 40, 9, 32, 24, 16, 40,
                                                 12)]
        gens = [24, 16, 32, 24, 32, 24, 16, 24]
        refs = [_direct_stream(engines[1], p, g)
                for p, g in zip(prompts, gens)]
        if cache:                    # the references must not pre-warm a tree
            for e in engines:
                while e.prefix_cache.cached_blocks \
                        and e.prefix_cache.evict(e.prefix_cache.cached_blocks):
                    pass
        frees = [e.free_blocks for e in engines]

        if scenario == "failover_rejoin":
            # deadlines that a warm step stays under even with every core
            # of the box taken by other test workers (a spurious down on
            # both replicas at once sheds); the injected stall outlasts them
            _, rt = _router(engines, health=dict(
                _HEALTH, auto_rejoin=True, suspect_after_s=1.0,
                down_after_s=3.0))
            # the whole mix once through each replica before the monitor
            # starts: what a first multi-row pass builds lazily would read
            # as a stall
            rt.cluster.start()
            for r in rt.cluster.frontends:
                for p, g in zip(prompts, gens):
                    r.frontend.submit(p, priority="lo", max_new_tokens=g)
                assert r.frontend.drain(timeout=120)
        else:
            roles, cfg = ((["prefill", "decode"],
                           {"topology": "disaggregated"})
                          if scenario == "disaggregated_handoff" else
                          (["serve", "serve"],
                           {"policy": "cache_aware", "balance": 16.0}))
            rt = ServingRouter(ServingCluster(engines, serving=_SERVING,
                                              roles=roles), cfg)
        rt.start()
        compiles = [e.compiles for e in engines]

        def submit(lo, hi):
            return [rt.submit(prompts[i], priority="hi" if i % 2 else "lo",
                              max_new_tokens=gens[i]) for i in range(lo, hi)]

        if scenario == "failover_rejoin":
            try:
                hs = submit(0, 4)
                _stream_until_first_token(hs[:2])
                fi.install(fi.parse_plan(
                    "serve.engine_step.r0:at=2:action=raise"))
                assert rt.drain(timeout=120)
                assert rt.health.wait_all_healthy(60.0)
                more = submit(4, 8)
                _stream_until_first_token(more[:2])
                fi.install(fi.parse_plan(
                    "serve.engine_step.r1:at=2:action=stall:delay_s=4.5"))
                assert rt.drain(timeout=120)
                assert rt.health.wait_all_healthy(60.0)
                hs += more
            finally:
                fi.clear()
            st = rt.health.stats
            assert st.liveness_downs >= 1 and st.stall_downs >= 1
            assert st.migrations >= 1 and st.rejoins >= 2
            _assert_migrated_chains(hs, tracer)
            # the timeline itself: lanes present, each request's hops bound
            # into one flow, and the injected raise's flight-recorder dump
            tracer.export()
            _trace_check(tmp_path, "--require", "serve", "serve/req",
                         "serve/router", "serve/health", "--require-flows",
                         "serve/req", "--expect-crash")
        elif scenario == "disaggregated_handoff":
            hs = submit(0, 8)
            assert rt.drain(timeout=120)
            assert rt.stats.handoffs == len(hs)
            assert rt.stats.handoff_bytes > 0
        else:
            prefilled = sum(e.scheduler.prefill_tokens_completed
                            for e in engines)
            hs = submit(0, 2)        # one request plants each prefix
            assert rt.drain(timeout=120)
            hs += submit(2, 8)       # the rest follow it to the warm tree
            assert rt.drain(timeout=120)
            assert rt.stats.cache_hit_blocks > 0
            prefilled = sum(e.scheduler.prefill_tokens_completed
                            for e in engines) - prefilled
            assert prefilled < sum(len(p) for p in prompts) - 5 * 32

        for h, ref in zip(hs, refs):
            assert h.status == "finished" and h.tokens == ref, \
                [(x.uid, x.status, x.migrated, len(x.tokens)) for x in hs]
        _assert_ledgers_tile(hs)
        rt.close()
        rt = None
        assert [e.compiles for e in engines] == compiles
        if cache:                    # cached pages are the tree's, not leaks
            for e in engines:
                assert e.free_blocks + e.prefix_cache.cached_blocks \
                    == e.allocator.total_blocks
        else:
            assert [e.free_blocks for e in engines] == frees
        assert locksan.edges()       # the proxies were in the path
        assert locksan.report()["cycles"] == []
        assert locksan.check_static(_static_lock_edges()) == set()
    finally:
        if rt is not None:
            rt.close()
        tracer.reset()
        locksan.disarm()
