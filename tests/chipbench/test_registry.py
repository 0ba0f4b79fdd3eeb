"""The harness finds every cell, configuration and metric by name, so a
later PR adds files and entries and edits nothing; and BENCHMARK.json, the
files it names and the contract's limits agree."""

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.harness import BenchError, Registry  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTHS = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|"
                    r"_rank$|head_dim|expansion|experts_per_tok")
CELLS = [w["name"] for w in BENCH["workloads"]]
E2E = {m["name"]: m for m in BENCH["end_to_end"]}


def cells_of(metric):
    return metric.get("workloads", CELLS)


def tree_digest(root):
    out = {}
    for base, _, files in os.walk(root):
        for f in files:
            p = os.path.join(base, f)
            out[os.path.relpath(p, root)] = hashlib.sha1(
                open(p, "rb").read()).hexdigest()
    return out


def test_new_files_are_discovered_without_an_edit(tmp_path):
    """A configuration, a traffic mix, a cell, a reader and a per-layer
    metric added as NEW files (and entries of BENCHMARK.json) are found."""
    root = str(tmp_path / "tree")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = tree_digest(os.path.join(root, "chipbench"))
    bench_dir = os.path.join(root, "chipbench")

    def put(rel, obj):
        path = os.path.join(bench_dir, rel)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            f.write(obj if isinstance(obj, str) else json.dumps(obj))

    base_cfg = json.load(open(os.path.join(
        bench_dir, "configs", BENCH["configs"][0]["name"] + ".json")))
    put("configs/newmodel-d3.json", dict(base_cfg, num_hidden_layers=3))
    put("traffic/new-mix.json", {"kind": "serve_open", "ramp_s": 1.0})
    put("workloads/newmodel.new-mix.json",
        {"config": "newmodel-d3", "traffic": "new-mix", "chips": 1,
         "driver": "serve_open", "why": "a cell added as files only"})
    put("readers/newreader.py",
        "def twice(view, name):\n    return 2 * view['counters'][name]\n")
    put("layer_metrics/new_metric.json",
        {"layer": "decode loop", "moves": "itl_p50_ms", "unit": "steps",
         "workloads": ["newmodel.new-mix"], "reader": "newreader.twice",
         "args": {"name": "decode_steps"}})
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["configs"].append({"name": "newmodel-d3", "source": "x",
                             "file": "chipbench/configs/newmodel-d3.json",
                             "reduced": ["num_hidden_layers"], "why": "y"})
    bench["workloads"].append({"name": "newmodel.new-mix",
                               "config": "newmodel-d3", "traffic": "new-mix",
                               "chips": 1, "why": "z"})
    bench["per_layer"].append({"name": "new_metric", "unit": "steps",
                               "better": "lower", "source": "program_counter",
                               "layer": "decode loop", "moves": "itl_p50_ms",
                               "workloads": ["newmodel.new-mix"]})
    for m in bench["end_to_end"]:
        if m["name"] == "itl_p50_ms":
            m["workloads"].append("newmodel.new-mix")
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))

    reg = Registry(root)
    cell = reg.cell("newmodel.new-mix")
    assert cell["driver"] == "serve_open" and cell["chips"] == 1
    assert reg.config(cell["config"])["num_hidden_layers"] == 3
    assert reg.traffic(cell["traffic"])["ramp_s"] == 1.0
    assert callable(reg.driver(cell["driver"]))
    names = [m["name"] for m in reg.metrics_of("newmodel.new-mix", "per_layer")]
    assert names == ["new_metric"]
    spec = reg.layer_metric("new_metric")
    view = {"counters": {"decode_steps": 21}}
    assert reg.reader(spec["reader"])(view, **spec["args"]) == 42
    assert {m["name"] for m in reg.metrics_of(
        "newmodel.new-mix", "end_to_end")} == {"itl_p50_ms", "setup_s"}
    after = tree_digest(bench_dir)
    assert {k: v for k, v in after.items() if k in before} == before, \
        "an existing file of the benchmark was edited"
    with pytest.raises(BenchError):
        reg.cell("no-such-cell")


def test_benchmark_keys_and_limits():
    assert set(BENCH) - {"trace_in_run"} == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench", "tests/chipbench"]
    assert BENCH["command"][1].startswith("chipbench/")
    n = 24       # the most cells later PRs may bring
    s = BENCH["run_seconds"]
    assert isinstance(s, int) and 1 <= s <= 51
    assert (2 + 14 * n) * (s + 60) + n * 2 * 90 + 1200 <= 43200
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_file(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"]) and len(cfg["why"]) <= 200
    assert cfg["file"] == f"chipbench/configs/{cfg['name']}.json"
    data = json.load(open(os.path.join(ROOT, cfg["file"])))
    assert data["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert data["reduced"] == cfg["reduced"]
    assert not any(WIDTHS.search(k) for k in cfg["reduced"])
    for key, published in data["published"].items():
        assert key in cfg["reduced"] and data[key] != published
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])
    # same source, same widths: only the reduced keys may differ
    for other in BENCH["configs"]:
        if other["source"] == cfg["source"]:
            theirs = json.load(open(os.path.join(ROOT, other["file"])))
            for k in ("hidden_size", "intermediate_size", "vocab_size",
                      "num_attention_heads", "num_key_value_heads"):
                assert theirs[k] == data[k]


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_file(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(cell[k]) for k in ("name", "config", "traffic"))
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    reg = Registry(ROOT)
    merged = reg.cell(cell["name"])
    data = json.load(open(os.path.join(
        ROOT, "chipbench", "workloads", cell["name"] + ".json")))
    for k in ("config", "traffic", "chips", "why"):
        assert data[k] == cell[k]
    assert cell["config"] in [c["name"] for c in BENCH["configs"]]
    reg.config(cell["config"]), reg.traffic(cell["traffic"])
    assert callable(reg.driver(merged["driver"]))
    e2e = [m["name"] for m in reg.metrics_of(cell["name"], "end_to_end")]
    assert sorted(e2e) == sorted(data["reports"])
    assert "setup_s" in e2e and len(e2e) >= 2
    assert reg.metrics_of(cell["name"], "per_layer")


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry(metric):
    per_layer = metric in BENCH["per_layer"]
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"layer", "moves"} if per_layer else {"bound"})
    assert set(metric) <= allowed and allowed - set(metric) <= {"workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert set(cells_of(metric)) <= set(CELLS)
    if not per_layer:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
        return
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    moved = E2E[metric["moves"]]
    assert set(cells_of(metric)) <= set(cells_of(moved)), \
        "moves a metric that some of its cells do not report"
    reg = Registry(ROOT)
    spec = reg.layer_metric(metric["name"])
    for k in ("layer", "moves", "unit", "workloads"):
        assert spec.get(k) == metric.get(k), k
    assert callable(reg.reader(spec["reader"]))
    assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200


def test_no_cell_or_configuration_name_in_python():
    names = CELLS + [c["name"] for c in BENCH["configs"]] + \
        [w["traffic"] for w in BENCH["workloads"]]
    for base, _, files in os.walk(os.path.join(ROOT, "chipbench")):
        for f in files:
            if f.endswith(".py"):
                text = open(os.path.join(base, f)).read()
                assert not [n for n in names if n in text], f


def test_file_names_use_allowed_characters():
    for path in BENCH["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_run_without_a_tpu_exits_2_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert "needs a TPU" in proc.stderr


class _AnyCounter(dict):
    """Every counter a driver could give, each 1.0."""

    def get(self, key, default=None):
        return 1.0

    def __getitem__(self, key):
        return 1.0


@pytest.mark.parametrize("cell", CELLS)
def test_result_line_of_each_cell(cell):
    """With every counter there and the recorded trace, the traced line
    carries each of the cell's per-layer metrics a reader can give and the
    plain line exactly its
    end-to-end metrics; a missing end-to-end value is an error."""
    from types import SimpleNamespace
    from chipbench import harness
    reg = Registry()
    dev = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite",
                          memory_stats=lambda: {"peak_bytes_in_use": 5 << 30})
    entry = reg.cell(cell)
    ctx = harness.Context(
        registry=reg, cell=entry, config=reg.config(entry["config"]),
        traffic=reg.traffic(entry["traffic"]), seed=1, seconds=1.0,
        devices=[dev] * entry["chips"],
        peaks=json.load(open(os.path.join(ROOT, "chipbench", "peaks.json")))[
            "TPU v5 lite"],
        compiles=None, t_process=0.0, on_chip=False,
        tracer=SimpleNamespace(path=os.path.join(
            os.path.dirname(__file__), "data", "tiny_trace.xplane.pb")))
    e2e = {m["name"] for m in reg.metrics_of(cell, "end_to_end")}
    out = harness.Outcome(correct=True, attempted=3, failed=0,
                          window_start=2.5, counters=_AnyCounter(),
                          end_to_end={n: 1.0 for n in e2e - {"setup_s"}})
    line = harness.result_line(ctx, out, trace=False)
    assert set(line["metrics"]) == e2e and set(entry["reports"]) == e2e
    assert line["metrics"]["setup_s"] == {"value": 2.5, "unit": "s"}
    assert line["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                              "count": entry["chips"],
                              "memory_peak_bytes": 5 << 30}
    traced = harness.result_line(ctx, out, trace=True)
    per_layer = {m["name"] for m in reg.metrics_of(cell, "per_layer")}
    # a reader that finds nothing gives nothing, and the metric is left
    # out: the tiny trace's one program runs under module_ms's 0.1 ms floor
    absent = per_layer - set(traced["metrics"])
    assert set(traced["metrics"]) <= per_layer
    # ... and the readers of readers/named.py and readers/span.py go by names
    # and spans that only a --trace 2 capture carries (test_trace2.py)
    assert all(reg.layer_metric(n)["reader"] == "trace.module_ms"
               or reg.layer_metric(n)["reader"].split(".")[0]
               in ("named", "span") for n in absent)
    assert traced["device"]["busy_s"] > 0 and traced["device"]["window_s"] > 0
    assert len(traced["breakdown"]["device_ops"]) <= 10
    out.end_to_end.clear()
    if e2e - {"setup_s"}:
        with pytest.raises(BenchError):
            harness.result_line(ctx, out, trace=False)
