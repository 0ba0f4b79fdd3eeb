"""``chipbench/readers/totals.py``: per-layer metrics summed from the
program's always-on totals; 0.0, not nothing, where the program counted
none (a program older than the counter gives the same line)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.harness import Registry  # noqa: E402
from deepspeed_tpu.monitor.trace import tracer  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
SETUP = [m for m in BENCH["per_layer"] if m["moves"] == "setup_s"]


@pytest.fixture
def totals(monkeypatch):
    made = {"setup/engine_init_s": 12.0, "setup/warmup_s": 38.0,
            "setup/warmup/decode_grid_s": 30.0,
            "compile/build/trace_s": 1.5, "compile/warmup/trace_s": 20.5,
            "compile/warmup/programs": 24.0, "compile/traffic/lower_s": 0.25}
    monkeypatch.setattr(tracer, "totals", made)
    return made


def test_read_sums_the_names_it_is_given(totals):
    read = Registry().reader("totals")
    view = {"values": {"setup_s": 100.0}}
    assert read(view, ["compile/build/trace_s",
                       "compile/warmup/trace_s"]) == 22.0
    assert read(view, ["setup/warmup_s"], scale=1e3) == 38e3
    assert read(view, ["compile/build/programs",
                       "compile/warmup/programs"]) == 24.0
    assert totals["compile/build/trace_s"] == 1.5        # a copy was read


def test_what_nobody_counted_reads_zero_not_nothing(totals):
    reg = Registry()
    view = {"values": {"setup_s": 100.0}}
    assert reg.reader("totals")(view, ["setup/remat_fit_s", "no/such"]) == 0.0
    assert reg.reader("totals.share_of_setup")(view, ["no/such"]) == 0.0
    totals.clear()                  # a program older than every counter
    assert reg.reader("totals")(view, ["setup/warmup_s"]) == 0.0


def test_share_of_setup_is_in_percent_of_the_runs_own_setup(totals):
    share = Registry().reader("totals.share_of_setup")
    names = ["setup/engine_init_s", "setup/warmup_s", "setup/first_step_s"]
    assert share({"values": {"setup_s": 100.0}}, names) == 50.0
    assert share({"values": {"setup_s": 200.0}}, names) == 25.0
    assert share({"values": {}}, names) == 0.0


@pytest.mark.parametrize("metric", SETUP, ids=lambda m: m["name"])
def test_each_metric_of_set_up_reads_the_totals(metric, totals):
    """Every per-layer metric that moves ``setup_s`` is a program counter
    read by this reader, from names the program writes."""
    reg = Registry()
    spec = reg.layer_metric(metric["name"])
    assert metric["source"] == "program_counter"
    assert metric["better"] == "lower"
    assert spec["reader"].split(".")[0] == "totals"
    assert all(n.startswith(("setup/", "compile/"))
               for n in spec["args"]["names"])
    value = reg.reader(spec["reader"])({"values": {"setup_s": 100.0}},
                                       **spec["args"])
    assert isinstance(value, float) and value >= 0.0


def test_every_cell_reports_eight_of_them():
    by_cell = {w["name"]: [m["name"] for m in SETUP
                           if w["name"] in m["workloads"]]
               for w in BENCH["workloads"]}
    assert len(SETUP) == 10
    for cell, names in by_cell.items():
        assert len(names) == 8, cell
        pair = {n.rsplit(".", 1)[1] for n in names if "." in n}
        assert pair in ({"serve"}, {"train"})
