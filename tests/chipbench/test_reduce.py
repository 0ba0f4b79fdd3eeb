"""The reduction from a device trace to numbers, on events written by hand
and on a small trace recorded on a v5e chip
(``chipbench/tools/record_tiny_trace.py``); and the benchmark's other
arithmetic (percentiles, latency from due time, FLOPs from shapes)."""

import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.reduce import flops, latency, stats, xplane  # noqa: E402
from chipbench.reduce.xplane import DeviceTrace, Event, Trace  # noqa: E402

TINY = os.path.join(os.path.dirname(__file__), "data", "tiny_trace.xplane.pb")

MOSAIC = ('%attn.1 = bf16[8,128]{1,0} custom-call(bf16[8,128]{1,0} %x), '
          'custom_call_target="tpu_custom_call"')
FUSION = "%fusion.3 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(bf16[8,128]{1,0} %a)"
GATHER_DONE = ("%all-gather-done.2 = bf16[32,128]{1,0} all-gather-done("
               "(bf16[8,128]{1,0}, bf16[32,128]{1,0}) %all-gather-start.2)")
ALLREDUCE = "%all-reduce.7 = f32[128]{0} all-reduce(f32[128]{0} %g), to_apply=%sum"
RS_FUSION = ("%fusion.101 = f32[16416,8,128]{2,1,0:T(8,128)S(1)} fusion("
             "f32[4,4095,4096]{1,2,0:T(8,128)} %get-tuple-element.4820), "
             "kind=kCustom, calls=%all-reduce-scatter.1")
AC_START = ("%async-collective-start.57 = (bf16[1,8]{1,0}, bf16[4,8]{1,0}) "
            "fusion(bf16[1,8]{1,0} %p), kind=kCustom, calls=%fused_computation.9")
AC_DONE = ("%async-collective-done.57 = bf16[4,8]{1,0} fusion((bf16[1,8]{1,0}, "
           "bf16[4,8]{1,0}) %async-collective-start.57), kind=kCustom, "
           "calls=%fused_computation.10")
MATMUL_OF_GATHER = ("%fusion.2323 = bf16[4096,1024]{1,0} fusion(bf16[1024,1024]"
                    "{1,0} %all-gather.2150), kind=kOutput, calls=%fused_computation.3")
PERMUTE_SPAN = ("%collective-permute-start.1 = (bf16[96,8,128]{2,1,0}, bf16[96,8,128]"
                "{2,1,0}) collective-permute-start(bf16[96,8,128]{2,1,0} %slice.247)")
COPY_SPAN = "%copy-start.30 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(f32[8]{0} %x)"
WHILE = "%while.1 = (s32[], bf16[8,128]{1,0}) while((s32[], bf16[8,128]{1,0}) %t)"


def hand_trace():
    """Two chips, a 1000 ns window. Chip 0: fusion 0-300, a while 400-800
    holding a Mosaic call 400-600 and a fusion 600-700 (100 ns its own),
    all-gather-done 900-1000. Chip 1: fusion 0-500, all-reduce 500-650."""
    d0 = DeviceTrace(
        ops=[Event(FUSION, 0, 300), Event(WHILE, 400, 400),
             Event(MOSAIC, 400, 200), Event(FUSION, 600, 100),
             Event(GATHER_DONE, 900, 100)],
        modules=[Event("jit_step(11)", 0, 800), Event("jit_tail(12)", 900, 100)])
    d1 = DeviceTrace(
        ops=[Event(FUSION, 0, 500), Event(ALLREDUCE, 500, 150)],
        modules=[Event("jit_step(11)", 0, 650)])
    host = [Event("fetch loss", 310, 80), Event("harness dispatch", 805, 20)]
    return Trace(devices={0: d0, 1: d1}, host=host)


def test_clipped_first_and_last_execution_are_left_out():
    mods = [Event("jit_step(1)", 0, 375), Event("jit_step(1)", 375, 501),
            Event("jit_step(1)", 876, 501), Event("jit_step(1)", 1377, 4)]
    tr = Trace(devices={0: DeviceTrace(ops=[Event(FUSION, 0, 1381)],
                                       modules=mods)})
    assert xplane.module_table(tr)[0]["calls"] == 2
    assert xplane.module_ms(tr) == pytest.approx(501e-6)


def test_names():
    assert xplane.opcode(FUSION) == "fusion"
    assert xplane.opcode(MOSAIC) == "custom-call"
    assert xplane.opcode(GATHER_DONE) == "all-gather-done"
    assert xplane.opcode(WHILE) == "while"
    assert xplane.instruction(FUSION) == "%fusion.3"
    assert xplane.is_mosaic(MOSAIC) and not xplane.is_mosaic(FUSION)
    assert xplane.is_collective(GATHER_DONE) and xplane.is_collective(ALLREDUCE)
    assert not xplane.is_collective(FUSION)
    # fusions the compiler makes of a collective count; a matmul that only
    # reads a gathered operand does not
    assert xplane.is_collective(RS_FUSION) and xplane.is_collective(AC_START)
    assert xplane.is_collective(AC_DONE)
    assert not xplane.is_collective(MATMUL_OF_GATHER)
    assert not xplane.is_collective(COPY_SPAN)
    assert xplane.module_name("jit_step(11)") == ("jit_step", "11")


def test_hand_trace_idle_busy_and_shares():
    tr = hand_trace()
    assert xplane.window(tr) == (0, 1000)
    busy = xplane.busy_seconds(tr)
    assert busy[0] == pytest.approx(800e-9)      # 300 + 400 + 100
    assert busy[1] == pytest.approx(650e-9)
    # worst chip is the one busy least: 1 - 650/1000
    assert xplane.idle_share(tr) == pytest.approx(0.35)
    selfs = {(e.name, e.start_ns): t for e, t in xplane.self_times(tr.devices[0].ops)}
    assert selfs[(WHILE, 400)] == 100            # 400 less its 300 of body
    # Mosaic: 200 of (800 + 650) busy nanoseconds
    assert xplane.op_share(tr, xplane.is_mosaic) == pytest.approx(200 / 1450)
    # exposed collectives: chip 0 100/1000, chip 1 150/1000; the worst
    assert xplane.exposed_collective_share(tr) == pytest.approx(0.15)


def test_exposed_collectives_count_fusions_and_idle_under_a_collective():
    """One chip, window 0-1000. Operations: fusion 0-200, a reduce-scatter
    fusion 200-300 (100 exposed), async-collective-start 300-310 (10),
    fusion 310-400, idle 400-500, async-collective-done 500-520 (20), fusion
    520-600, idle 600-700, fusion 700-1000. Collectives in flight: 310-500
    (start's end to done's start) and a collective-permute span 650-800 on
    the async line; a copy span 590-710 there is no collective. Idle under a
    collective: 400-500 (100) and 650-700 (50). Exposed 130 + 150 = 280."""
    ops = [Event(FUSION, 0, 200), Event(RS_FUSION, 200, 100),
           Event(AC_START, 300, 10), Event(FUSION, 310, 90),
           Event(AC_DONE, 500, 20), Event(FUSION, 520, 80),
           Event(FUSION, 700, 300)]
    dev = DeviceTrace(ops=ops, async_ops=[Event(PERMUTE_SPAN, 650, 150),
                                          Event(COPY_SPAN, 590, 120)])
    assert xplane.collective_spans(dev) == [(310, 500), (650, 800)]
    assert xplane.overlap_ns([(400, 500), (600, 700)],
                             [(310, 500), (650, 800)]) == 150
    tr = Trace(devices={0: dev})
    assert xplane.exposed_collective_share(tr) == pytest.approx(0.28)
    assert xplane.idle_share(tr) == pytest.approx(0.2)


def test_hand_trace_modules_ops_and_gaps():
    tr = hand_trace()
    table = xplane.module_table(tr)
    assert table[0]["module"] == "jit_step(11)" and table[0]["calls"] == 2
    assert table[0]["mean_ms"] == pytest.approx((800 + 650) / 2 * 1e-6)
    assert xplane.module_ms(tr) == pytest.approx(725e-6)
    assert xplane.module_ms(tr, min_mean_ms=1.0) is None
    top = dict(map(tuple, xplane.top_ops(tr, 10)))
    assert top["jit_step/%fusion.3 fusion"] == pytest.approx(900e-9)
    assert top["jit_step/%attn.1 mosaic"] == pytest.approx(200e-9)
    assert top["jit_tail/%all-gather-done.2 all-gather-done"] == \
        pytest.approx(100e-9)
    # chip 0's gaps are 300-400 and 800-900, both under a millisecond
    assert xplane.idle_gaps(tr) == [["unattributed (<0.1 ms)",
                                     pytest.approx(200e-9)]]


def test_long_gap_goes_to_the_annotation_that_covers_it():
    ops = [Event(FUSION, 0, 1e6), Event(FUSION, 6e6, 1e6),
           Event(FUSION, 10e6, 1e6)]
    host = [Event("waiting for arrival", 1.5e6, 4e6)]
    tr = Trace(devices={0: DeviceTrace(ops=ops)}, host=host)
    gaps = dict(map(tuple, xplane.idle_gaps(tr)))
    assert gaps["waiting for arrival"] == pytest.approx(5e-3)
    assert gaps["unattributed (1-10 ms)"] == pytest.approx(3e-3)


def test_recorded_trace_against_values_worked_out_by_hand():
    """Three runs of ``jit_tiny_step`` of seven operations each. By hand from
    the dump of the recording (nanoseconds): durations per run
    13+2+15083+2292+27781+2282+13128, 14+3+14982+2292+27781+2283+13123 and
    13+2+15027+2291+27782+2283+13126 = 181,583 busy; first operation at
    47,746,489, last ends 70,447,330; the Mosaic call 27,781+27,781+27,782;
    modules 60,597 60,492 60,541; a 20 ms sleep under ``harness sleep``
    before the third run."""
    tr = xplane.load(TINY, ["harness sleep", "harness step"])
    assert list(tr.devices) == [0]
    assert len(tr.devices[0].ops) == 21 and len(tr.devices[0].modules) == 3
    assert [e.name for e in tr.host].count("harness step") == 3
    t0, t1 = xplane.window(tr)
    assert t1 - t0 == pytest.approx(70447330 - 47746489, abs=5)
    assert xplane.busy_seconds(tr)[0] == pytest.approx(181583e-9, rel=1e-4)
    assert xplane.idle_share(tr) == pytest.approx(
        1 - 181583 / 22700841, rel=1e-6)
    assert xplane.op_share(tr, xplane.is_mosaic) == pytest.approx(
        83344 / 181583, rel=1e-4)
    assert xplane.exposed_collective_share(tr) == 0.0
    # of three runs the first and last may be clipped by the trace: the middle
    assert xplane.module_ms(tr) == pytest.approx(60492.5e-6, rel=1e-5)
    top = xplane.top_ops(tr, 3)
    assert top[0][0] == "jit_tiny_step/%tiny_step.1 mosaic"
    gaps = dict(map(tuple, xplane.idle_gaps(tr)))
    # the gap before the third run, 48,565,454 to 70,386,796
    assert gaps["harness sleep"] == pytest.approx(21.82e-3, rel=1e-3)


@pytest.mark.parametrize("q", [0, 50, 90, 95, 100])
def test_percentile_is_numpys(q):
    xs = np.random.default_rng(3).lognormal(size=237)
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert stats.percentile([], q) is None


def handle(arrival, ttft, gaps, admit=None, status="finished", tokens=None):
    return SimpleNamespace(arrival_t=arrival, ttft_ms=ttft, tbt_ms=gaps,
                           admit_t=admit, status=status,
                           tokens=tokens if tokens is not None
                           else [1] * (1 + len(gaps)), finished=True)


def test_latency_counts_from_the_due_time():
    # due at 10.0 s, reached the server 0.25 s late, first token 100 ms on
    sent = SimpleNamespace(due_t=10.0, request=SimpleNamespace(max_new_tokens=3),
                           handle=handle(10.25, 100.0, [20.0, 0.0], admit=10.3))
    assert latency.lateness_ms(sent) == pytest.approx(250.0)
    assert latency.ttft_ms(sent) == pytest.approx(350.0)
    assert latency.queue_wait_ms(sent) == pytest.approx(300.0)
    assert latency.token_times(sent.handle) == pytest.approx(
        [10.35, 10.37, 10.37])
    assert latency.tokens_between([sent], 10.36, 11.0) == 2
    assert latency.tokens_between([sent], 0.0, 10.36) == 1
    assert latency.complete(sent, vocab=10)
    sent.handle.tokens = [1, 2]
    assert not latency.complete(sent, vocab=10)
    sent.handle.tokens = [1, 2, 11]
    assert not latency.complete(sent, vocab=10)
    none = SimpleNamespace(due_t=1.0, handle=handle(1.0, None, []))
    assert latency.ttft_ms(none) is None and latency.token_times(none.handle) == []


def test_flops_from_shapes():
    mistral = dict(hidden_size=4096, intermediate_size=14336, vocab_size=32000,
                   num_attention_heads=32, num_key_value_heads=8,
                   num_hidden_layers=32, sliding_window=4096)
    # 7.24B parameters less the 131M-row embedding and the norm gains
    assert flops.matmul_params(mistral) == 32 * (
        4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336) + 4096 * 32000
    assert flops.matmul_params(mistral) == pytest.approx(7.11e9, rel=2e-3)
    # causal attention over 4096 positions: (T + 1) / 2 keys on average
    per_tok = flops.attention_flops_per_token(mistral, 4096, training=False)
    assert per_tok == pytest.approx(32 * 4 * 32 * 128 * 2048.5)
    assert flops.attention_flops_per_token(
        dict(mistral, sliding_window=1024), 4096, False) < per_tok
    assert flops.train_flops_per_token(mistral, 4096) == pytest.approx(
        6 * flops.matmul_params(mistral) + 3 * per_tok)
    mixtral = dict(mistral, num_local_experts=8, num_experts_per_tok=2,
                   sliding_window=None, num_hidden_layers=1)
    dense = dict(mistral, num_hidden_layers=1)
    assert flops.matmul_params(mixtral) - flops.matmul_params(dense) == \
        3 * 4096 * 14336 + 4096 * 8
    assert flops.mfu_percent(1000.0, 197e9, 197e12) == pytest.approx(100.0)
