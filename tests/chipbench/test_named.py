"""The readers that go by the program's own names — jitted programs by name,
operations by ``jax.named_scope``, the program's spans from a capture — on
events written by hand and on a small trace recorded on a v5e chip through
``tracer.capture_start`` / ``capture_stop``
(``chipbench/tools/record_scoped_trace.py``), kept with what ``capture_stop``
returned."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.harness import ANNOTATIONS, Registry  # noqa: E402
from chipbench.reduce import flash_flops, hlo_names, named, xplane  # noqa: E402
from chipbench.reduce.xplane import DeviceTrace, Event, Trace  # noqa: E402
from deepspeed_tpu.monitor.trace import Capture  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")
SCOPED = os.path.join(DATA, "tiny_trace_scoped.xplane.pb")
PROGRAM = "jit_tiny_scoped_step(5260113293154064000)"


def kept_capture() -> Capture:
    with open(os.path.join(DATA, "tiny_trace_scoped.capture.json")) as f:
        doc = json.load(f)
    doc["records"] = [tuple(r) for r in doc["records"]]
    return Capture(trace_path=SCOPED, **doc)


def mosaic(instr, shape="bf16[1,32,4096,128]"):
    return (f"%{instr} = {shape}{{3,2,1,0}} custom-call({shape}{{3,2,1,0}} "
            f'%q, {shape}{{3,2,1,0}} %k), custom_call_target="tpu_custom_call"')


FUSION = "%fusion.3 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %a)"
SLICE = "%dynamic-slice_bitcast_fusion.2 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %w)"
AG_START = ("%all-gather-start.2 = (bf16[8,128]{1,0}, bf16[32,128]{1,0}) "
            "all-gather-start(bf16[8,128]{1,0} %p)")
AG_DONE = ("%all-gather-done.2 = bf16[32,128]{1,0} all-gather-done("
           "(bf16[8,128]{1,0}, bf16[32,128]{1,0}) %all-gather-start.2)")


def hand_trace():
    """One chip, 1000 ns. A decode step 0-400 (fusion 0-100, a paged kernel
    100-300, a weight slice 300-400), a prefill pass 400-700 (flash-packed
    kernel 400-600, fusion 600-700), a decode step 800-1000 at another
    bucket (fusion only), and one more of each at the ends so that the
    clipped first and last executions can be left out."""
    ops = [Event(FUSION, 0, 100), Event(mosaic("closed_call.21"), 100, 200),
           Event(SLICE, 300, 100), Event(mosaic("closed_call.7"), 400, 200),
           Event(FUSION, 600, 100), Event(FUSION, 800, 200)]
    modules = [Event("jit_serve_decode_step(1)", -50, 40),
               Event("jit_serve_decode_step(1)", 0, 400),
               Event("jit_serve_prefill_packed(2)", 400, 300),
               Event("jit_serve_decode_step_sk2(3)", 800, 200),
               Event("jit_serve_decode_step(1)", 1010, 5)]
    return Trace(devices={0: DeviceTrace(ops=ops, modules=modules)})


OP_NAMES = {
    "jit_serve_decode_step(1)": {
        "closed_call.21": "jit(serve_decode_step)/while/body/attn/paged_decode_step/pallas_call",
        "fusion.3": "jit(serve_decode_step)/while/body/ffn/dot_general"},
    "jit_serve_prefill_packed(2)": {
        "closed_call.7": "jit(serve_prefill_packed)/while/body/attn/flash_fwd_packed/pallas_call",
        "fusion.3": "jit(serve_prefill_packed)/while/body/moe_ffn/experts/ragged_dot"},
}


def test_program_ms_goes_by_name_whatever_ran_most():
    tr = hand_trace()
    # (400 + 200) / 2: both buckets' programs, the clipped ends left out
    assert named.program_ms(tr, "jit_serve_decode_step") == pytest.approx(3e-4)
    assert named.program_ms(tr, "jit_serve_prefill") == pytest.approx(3e-4)
    assert named.program_ms(tr, "jit_serve_verify") is None
    # the old reading follows the count: one more run of another bucket
    # would change it without any program changing
    assert xplane.module_ms(tr) == pytest.approx(4e-4)


def test_program_share_is_busy_time_inside_the_programs():
    tr = hand_trace()
    assert named.program_share(tr, ["jit_serve_prefill_packed",
                                    "jit_serve_paged_pass"]) \
        == pytest.approx(300 / 900)
    assert named.program_share(tr, ["jit_serve_decode_step"]) \
        == pytest.approx(600 / 900)
    assert named.program_share(tr, ["jit_train"]) is None


def test_scope_share_and_calls_by_op_name():
    tr = hand_trace()
    assert named.scope_share(tr, OP_NAMES, "attn") == pytest.approx(400 / 900)
    assert named.scope_share(tr, OP_NAMES, "paged_decode_step") \
        == pytest.approx(200 / 900)
    assert named.scope_share(tr, OP_NAMES, "moe_ffn") == pytest.approx(100 / 900)
    assert named.scope_share(tr, OP_NAMES, "paged_decode_step|flash_fwd_packed") \
        == pytest.approx(400 / 900)
    assert named.scope_share(tr, OP_NAMES, "flash_fwd") is None   # not a prefix match
    assert named.scope_share(tr, {}, "attn") is None
    # XLA's own kernel keeps no op_name: it counts by its instruction's name
    assert named.scope_share(tr, OP_NAMES, "moe_ffn", ["%dynamic-slice"]) \
        == pytest.approx(200 / 900)
    calls = named.scope_calls(tr, OP_NAMES, "attn")
    assert [(xplane.instruction(e.name), t) for e, t in calls] == [
        ("%closed_call.21", 200), ("%closed_call.7", 200)]
    # an operation outside any program's execution has no name
    assert all(n == "" for e, _, n in named.named_ops(tr, OP_NAMES)
               if e.start_ns == 800)


@pytest.mark.parametrize("op_name,scope,hit", [
    ("jit(f)/jvp(flash_fwd)/pallas_call", "flash_fwd", True),
    ("jit(f)/transpose(jvp(flash_bwd_dq))/pallas_call", "flash_bwd_dq", True),
    ("jit(f)/transpose(jvp(flash_bwd_dq))/pallas_call", "flash_bwd_dq|flash_bwd_dkv", True),
    ("jit(f)/flash_fwd_packed/pallas_call", "flash_fwd", False),
    ("jit(f)/checkpoint/flash_fwd/pallas_call", "flash_fwd", True),
    ("jit(f)/zero3/gather/w3/all_gather", "zero3/gather/w3", True),
    ("jit(f)/zero3/gather/w31/all_gather", "zero3/gather/w3", False),
    ("flash_fwd", "flash_fwd", True),
])
def test_scope_pattern(op_name, scope, hit):
    assert bool(hlo_names.scope_pattern(scope).search(op_name)) is hit


def test_hidden_collective_share_on_hand_events():
    """An all-gather in flight 100-900: the operations line computes under it
    200-600 (hidden), stands idle 600-800 (exposed) and waits in its -done
    800-900 (exposed)."""
    dev = DeviceTrace(ops=[Event(FUSION, 0, 90), Event(AG_START, 90, 10),
                           Event(FUSION, 200, 400), Event(AG_DONE, 800, 100),
                           Event(FUSION, 900, 100)])
    tr = Trace(devices={0: dev})
    assert named.hidden_collective_share(tr) == pytest.approx(400 / 1000)
    assert xplane.exposed_collective_share(tr) == pytest.approx(
        (10 + 100 + 100 + 200) / 1000)
    assert named.hidden_collective_share(hand_trace()) is None


def test_flash_flops_count_what_the_kernel_computes():
    from deepspeed_tpu.ops.pallas import flash_attention as kernel
    for t in (128, 1024, 1032, 4096, 6144):
        assert flash_flops.pick_block(t) == kernel._pick_block(
            t, kernel.DEFAULT_BLOCK_Q)
    assert kernel.DEFAULT_BLOCK_Q == kernel.DEFAULT_BLOCK_K \
        == flash_flops.DEFAULT_BLOCK
    # 4 x 4 tiles of 1024 at seq 4096: the mask skips the 6 above the diagonal
    assert flash_flops.computed_tiles(4096, 4096, 1024, 1024, True) == 10
    assert flash_flops.computed_tiles(4096, 4096, 1024, 1024, False) == 16
    assert flash_flops.computed_tiles(1024, 1024, 1024, 1024, True) == 1
    fwd = flash_flops.call_flops("flash_fwd", 1, 32, 4096, 128)
    assert fwd == 2 * 2 * 1024 * 1024 * 128 * 10 * 32
    assert flash_flops.call_flops("flash_bwd_dq", 1, 32, 4096, 128) == 1.5 * fwd
    assert flash_flops.call_flops("flash_bwd_dkv", 1, 32, 4096, 128) == 2 * fwd
    assert named.first_operand_shape(mosaic("x")) == (1, 32, 4096, 128)
    assert named.first_operand_shape(FUSION) is None


# --------------------------------------------------------------------------- #
# the recorded trace
# --------------------------------------------------------------------------- #

def test_recorded_trace_carries_the_scopes_in_its_hlo():
    names = hlo_names.load(SCOPED)
    assert list(names) == [PROGRAM]
    kernels = {op for op in names[PROGRAM].values() if "pallas_call" in op}
    assert kernels == {
        "jit(tiny_scoped_step)/jvp(flash_fwd)/pallas_call",
        "jit(tiny_scoped_step)/transpose(jvp(flash_bwd_dq))/pallas_call",
        "jit(tiny_scoped_step)/transpose(jvp(flash_bwd_dkv))/pallas_call"}
    # the trace PR 24 kept predates the scopes: its HLO is there, the names not
    old = hlo_names.load(os.path.join(DATA, "tiny_trace.xplane.pb"))
    assert list(old) == ["jit_tiny_step(1045019847805046428)"]
    assert not any("flash" in op for op in old[list(old)[0]].values())


def test_recorded_trace_splits_the_kernels_by_scope():
    tr = xplane.load(SCOPED, ANNOTATIONS)
    names = hlo_names.load(SCOPED)
    fwd = named.scope_share(tr, names, "flash_fwd")
    bwd = named.scope_share(tr, names, "flash_bwd_dq|flash_bwd_dkv")
    # 100.8, 108.5 and 145.3 us of a 452 us step: the three scopes are all of
    # the Mosaic time and nothing else is
    assert fwd == pytest.approx(100.8 / 452.2, abs=0.005)
    assert bwd == pytest.approx((108.5 + 145.3) / 452.2, abs=0.005)
    assert fwd + bwd == pytest.approx(xplane.op_share(tr, xplane.is_mosaic))
    assert len(named.scope_calls(tr, names, "flash_fwd")) == 3
    assert named.program_ms(tr, "jit_tiny_scoped") == pytest.approx(
        xplane.module_ms(tr))
    assert named.program_share(tr, ["jit_tiny_scoped"]) == pytest.approx(1.0)
    top = xplane.top_ops(tr, 10, names)
    assert any(k.endswith("mosaic @jit(tiny_scoped_step)/jvp(flash_fwd)")
               for k, _ in top)


def test_recorded_capture_readers():
    """The metric files' readers on the recorded trace and capture: a share
    of a peak stays under 100%, and a reader that needs the capture gives
    nothing without it."""
    reg = Registry()
    capture = kept_capture()
    tr = xplane.load(SCOPED, ANNOTATIONS)
    view = {"trace": tr, "capture": capture, "op_names": hlo_names.load(SCOPED),
            "peaks": {"bf16_flops_per_s": 197e12}}
    roofline = reg.reader("named.flash_roofline_share")(view)
    # T 2048, 8 heads of 128: 3 of 4 tiles computed; small calls of 100 us
    assert 20.0 < roofline < 100.0
    assert reg.reader("named.scope_share")(view, scope="flash_fwd") \
        == pytest.approx(22.3, abs=0.5)
    assert reg.reader("named.scope_share")(view, scope="moe_ffn") is None
    step_share = reg.reader("span.share")(view, names=["host/step"])
    sleep_share = reg.reader("span.share")(view, names=["host/sleep"])
    assert sleep_share == pytest.approx(100 * 0.02 / 0.0248, rel=0.1)
    # the three steps and the sleep tile their own extent but for the
    # moments between them; of the captured interval they leave the ends
    loose = reg.reader("span.unaccounted_share")(
        view, names=["host/step", "host/sleep"])
    assert 0.0 < loose < 100.0 - step_share - sleep_share < 1.5
    assert reg.reader("span.share")(
        view, names=["host/sleep"], within=["host/step", "host/sleep"]) \
        == pytest.approx(100 * 20.547 / 24.722, rel=0.01)
    assert reg.reader("span.share")(view, names=["host/sleep"],
                                    within=["serve/loop/idle"]) is None
    per_step = reg.reader("span.ms_per")(view, names=["host/step"],
                                         per="host/step_added")
    assert 0.5 < per_step < 2.0
    assert reg.reader("span.share")(view, names=["serve/prefill/pass"]) is None
    bare = {"trace": tr, "peaks": view["peaks"]}
    for reader, args in (("span.share", {"names": ["host/step"]}),
                         ("span.unaccounted_share", {"names": ["host/step"]}),
                         ("span.ms_per", {"names": ["host/step"],
                                          "per": "host/step"}),
                         ("named.scope_share", {"scope": "flash_fwd"}),
                         ("named.flash_roofline_share", {})):
        assert reg.reader(reader)(bare, **args) is None


def test_program_spans_take_idle_gaps():
    """The 21 ms the device stood idle go to the program's span over them,
    not to ``unattributed``."""
    capture = kept_capture()
    tr = xplane.load(SCOPED, ANNOTATIONS)
    gaps = dict(xplane.idle_gaps(tr, 10))
    assert any(k.startswith("unattributed (>10 ms)") for k in gaps)
    tr.host.extend(Event(r[1], r[2], r[3] - r[2]) for r in capture.records)
    tr.host.sort(key=lambda e: e.start_ns)
    gaps = dict(xplane.idle_gaps(tr, 10))
    assert gaps["host/sleep"] == pytest.approx(0.0213, rel=0.02)
    assert not any(k.startswith("unattributed (>10 ms)") for k in gaps)


def test_of_nested_spans_the_inner_takes_the_gap():
    """A decode slice holds a step, which holds its drain: a gap inside the
    drain overlaps all three alike and goes to the drain."""
    dev = DeviceTrace(ops=[Event(FUSION, 0, 1e6), Event(FUSION, 5e6, 1e6)])
    host = [Event("serve/loop/decode_slice", 0, 9e6),
            Event("serve/decode/step", 0.5e6, 6e6),
            Event("serve/decode/drain", 0.9e6, 4.5e6),
            Event("serve/decode/dispatch", 0.5e6, 0.4e6)]
    tr = Trace(devices={0: dev}, host=host)
    assert xplane.idle_gaps(tr, 10) == [["serve/decode/drain", 4e-3]]
    tr.host = host[:1] + [Event("fetch loss", 2e6, 1e6)]
    assert xplane.idle_gaps(tr, 10) == [["serve/loop/decode_slice", 4e-3]]
