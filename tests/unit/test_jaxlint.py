"""jaxlint unit tests: one failing and one passing fixture per rule, plus the
suppression, baseline, config, and CLI machinery."""

import json
import textwrap

import pytest

from deepspeed_tpu.tools.jaxlint import (LintConfig, RULE_REGISTRY,
                                         RuleSettings, lint_text)
from deepspeed_tpu.tools.jaxlint.baseline import (apply_baseline,
                                                  load_baseline,
                                                  write_baseline)
from deepspeed_tpu.tools.jaxlint.cli import main as jaxlint_main


def lint(src, **rule_options):
    cfg = LintConfig()
    for rid, opts in rule_options.items():
        cfg.rules[rid] = RuleSettings(options=opts)
    return lint_text(textwrap.dedent(src), path="pkg/mod.py", config=cfg)


def rules_of(findings):
    return [f.rule for f in findings]


def test_registry_has_all_seven_rules():
    assert set(RULE_REGISTRY) == {"JL001", "JL002", "JL003", "JL004",
                                  "JL005", "JL007", "JL008"}


# --------------------------------------------------------------------------- #
# JL001 — untimed async dispatch
# --------------------------------------------------------------------------- #

def test_jl001_flags_unsynced_delta():
    findings = lint("""
        import time

        def bench(f, x):
            t0 = time.time()
            y = f(x)
            return time.time() - t0
    """)
    assert rules_of(findings) == ["JL001"]


def test_jl001_clean_with_block_until_ready():
    findings = lint("""
        import time
        import jax

        def bench(f, x):
            t0 = time.time()
            y = f(x)
            jax.block_until_ready(y)
            return time.time() - t0
    """)
    assert findings == []


def test_jl001_ignores_pure_host_timing():
    # no significant call inside the timed window: nothing to sync
    findings = lint("""
        import time

        def tick():
            t0 = time.time()
            return time.time() - t0
    """)
    assert findings == []


def test_jl001_reassigned_clock_var_uses_latest_stamp():
    # the second window is pure-host: re-stamping t0 must reset the window,
    # not stretch it back over the earlier dispatch
    findings = lint("""
        import time
        import jax

        def two_windows(f, parse, x):
            t0 = time.time()
            y = f(x)
            jax.block_until_ready(y)
            d1 = time.time() - t0
            t0 = time.time()
            parse(x)
            d2 = time.time() - t0
            return d1, d2
    """)
    assert rules_of(findings) == ["JL001"]  # only the unsynced second window
    assert findings[0].line == 12


def test_jl001_perf_counter_and_aliased_start():
    findings = lint("""
        import time

        def bench(g):
            start = time.perf_counter()
            g()
            dt = time.perf_counter() - start
            return dt
    """)
    assert rules_of(findings) == ["JL001"]


# --------------------------------------------------------------------------- #
# JL002 — constant PRNG keys
# --------------------------------------------------------------------------- #

def test_jl002_flags_constant_key():
    findings = lint("""
        import jax

        def init(shape):
            key = jax.random.PRNGKey(0)
            return jax.random.normal(key, shape)
    """)
    assert rules_of(findings) == ["JL002"]


def test_jl002_clean_with_threaded_rng():
    findings = lint("""
        import jax
        from deepspeed_tpu.utils.rng import default_rng

        def init(shape, rng=None):
            rng = rng if rng is not None else default_rng()
            return jax.random.normal(rng, shape)
    """)
    assert findings == []


def test_jl002_variable_seed_is_fine():
    findings = lint("""
        import jax

        def keyed(seed):
            return jax.random.PRNGKey(seed)
    """)
    assert findings == []


def test_jl002_allow_paths_skips_tests():
    src = """
        import jax
        KEY = jax.random.PRNGKey(0)
    """
    cfg = LintConfig()
    findings = lint_text(textwrap.dedent(src), path="tests/unit/test_x.py",
                         config=cfg)
    assert findings == []


def test_jl002_resolves_import_alias():
    findings = lint("""
        from jax import random as jrandom

        def init():
            return jrandom.PRNGKey(42)
    """)
    assert rules_of(findings) == ["JL002"]


def test_jl002_keyword_seed_form():
    findings = lint("""
        import jax

        def init():
            return jax.random.PRNGKey(seed=0)
    """)
    assert rules_of(findings) == ["JL002"]


def test_plain_dotted_import_does_not_corrupt_resolution():
    # `import jax.random` binds only `jax`; jax.jit must still resolve so
    # donation tracking works in such modules
    findings = lint("""
        import jax.random

        step = jax.jit(lambda s: s, donate_argnums=(0,))

        def run(state):
            out = step(state)
            print(state)
            return out
    """)
    assert rules_of(findings) == ["JL003"]


# --------------------------------------------------------------------------- #
# JL003 — donated-buffer reuse
# --------------------------------------------------------------------------- #

def test_jl003_flags_reread_after_donation():
    findings = lint("""
        import jax

        step = jax.jit(lambda s, b: s, donate_argnums=(0,))

        def train(state, batch):
            new_state = step(state, batch)
            print(state)          # reads the donated tree
            return new_state
    """)
    assert rules_of(findings) == ["JL003"]


def test_jl003_clean_when_rebound():
    findings = lint("""
        import jax

        step = jax.jit(lambda s, b: s, donate_argnums=(0,))

        def train(state, batch):
            state = step(state, batch)
            print(state)          # the NEW state: fine
            return state
    """)
    assert findings == []


def test_jl003_partial_decorator_and_loop_rebind():
    findings = lint("""
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def step(s, b):
            return s

        def train(state, batches):
            for b in batches:
                state = step(state, b)
            return state
    """)
    assert findings == []


def test_jl003_flags_stale_attribute_alias():
    # the autotuner bug shape: donate a tree read from an attribute, never
    # rebind the attribute -> the holder keeps referencing freed buffers
    findings = lint("""
        import jax

        step = jax.jit(lambda s, b: s, donate_argnums=(0,))

        def measure(engine, batch):
            state = engine.state
            state = step(state, batch)
            return state
    """)
    assert rules_of(findings) == ["JL003"]


def test_jl003_clean_when_attribute_rebound():
    findings = lint("""
        import jax

        step = jax.jit(lambda s, b: s, donate_argnums=(0,))

        def measure(engine, batch):
            state = engine.state
            state = step(state, batch)
            engine.state = state
            return state
    """)
    assert findings == []


def test_jl003_assume_donated_config():
    src = """
        def measure(probe, batch):
            compiled = probe.compiled
            state = probe.state
            out = compiled(state, batch)
            return out
    """
    assert rules_of(lint(src, JL003={"assume_donated": {"compiled": [0]}})) \
        == ["JL003"]
    assert lint(src) == []


# --------------------------------------------------------------------------- #
# JL004 — tracer control flow
# --------------------------------------------------------------------------- #

def test_jl004_flags_if_on_tracer():
    findings = lint("""
        import jax

        @jax.jit
        def f(x):
            if x > 0:
                return x
            return -x
    """)
    assert rules_of(findings) == ["JL004"]


def test_jl004_shape_checks_are_static():
    findings = lint("""
        import jax

        @jax.jit
        def f(x):
            if x.shape[0] > 1:
                return x[:1]
            return x
    """)
    assert findings == []


def test_jl004_static_argnums_excluded():
    findings = lint("""
        import functools
        import jax

        @functools.partial(jax.jit, static_argnums=(1,))
        def f(x, mode):
            if mode:
                return x * 2
            return x
    """)
    assert findings == []


def test_jl004_while_on_tracer_via_jit_call():
    findings = lint("""
        import jax

        def body(x):
            while x > 0:
                x = x - 1
            return x

        g = jax.jit(body)
    """)
    assert rules_of(findings) == ["JL004"]


def test_jl004_len_and_isinstance_are_host():
    findings = lint("""
        import jax

        @jax.jit
        def f(xs):
            if len(xs) > 2:
                return xs[0]
            return xs[-1]
    """)
    assert findings == []


# --------------------------------------------------------------------------- #
# JL005 — undeclared mesh axes
# --------------------------------------------------------------------------- #

def test_jl005_flags_unknown_axis():
    findings = lint("""
        import numpy as np
        import jax
        from jax.sharding import Mesh, PartitionSpec

        mesh = Mesh(np.array(jax.devices()), ("data",))
        spec = PartitionSpec("modle")   # typo'd axis
    """)
    assert rules_of(findings) == ["JL005"]


def test_jl005_clean_with_declared_axis():
    findings = lint("""
        import numpy as np
        import jax
        from jax.sharding import Mesh, PartitionSpec

        mesh = Mesh(np.array(jax.devices()), ("data", "model"))
        spec = PartitionSpec("data", "model")
    """)
    assert findings == []


def test_jl005_known_axes_config():
    src = """
        from jax.sharding import PartitionSpec as P
        spec = P("tensor")
    """
    assert lint(src) == []  # no mesh, no config: module skipped
    assert rules_of(lint(src, JL005={"known_axes": ["data"]})) == ["JL005"]
    assert lint(src, JL005={"known_axes": ["tensor"]}) == []


def test_jl005_collective_axis_name():
    findings = lint("""
        import jax
        from jax import lax

        def f(x):
            return lax.psum(x, axis_name="bogus")
    """, JL005={"known_axes": ["data"]})
    assert rules_of(findings) == ["JL005"]


def test_jl005_axis_index_first_positional():
    src = """
        from jax import lax

        def f():
            return lax.axis_index("dtaa")
    """
    assert rules_of(lint(src, JL005={"known_axes": ["data"]})) == ["JL005"]
    assert lint(src.replace("dtaa", "data"),
                JL005={"known_axes": ["data"]}) == []


# --------------------------------------------------------------------------- #
# JL007 — blocking host fetch in a hot-path module
# --------------------------------------------------------------------------- #

HOT = {"JL007": {"hot_paths": ["pkg/"]}}


def test_jl007_flags_bare_asarray_in_hot_path():
    findings = lint("""
        import numpy as np

        def drain(arr):
            return np.asarray(arr)
    """, **HOT)
    assert rules_of(findings) == ["JL007"]


def test_jl007_flags_device_get_item_tolist():
    findings = lint("""
        import jax

        def leak(arr):
            a = jax.device_get(arr)
            b = arr.item()
            c = arr.tolist()
            return a, b, c
    """, **HOT)
    assert rules_of(findings) == ["JL007", "JL007", "JL007"]


def test_jl007_dtyped_asarray_is_host_side():
    # an explicit dtype marks a host conversion, not a device drain
    findings = lint("""
        import numpy as np

        def convert(tokens):
            a = np.asarray(tokens, np.int32)
            b = np.asarray(tokens, dtype=np.int64)
            return a, b
    """, **HOT)
    assert findings == []


def test_jl007_inert_without_hot_path_config():
    # default options carry no hot_paths: the rule must not fire tree-wide
    findings = lint("""
        import numpy as np

        def drain(arr):
            return np.asarray(arr)
    """)
    assert findings == []


def test_jl007_non_hot_module_skipped():
    src = "import numpy as np\nhost = np.asarray(object())\n"
    cfg = LintConfig(rules={"JL007": RuleSettings(
        options={"hot_paths": ["inference/v2/"]})})
    assert lint_text(src, path="pkg/training/loop.py", config=cfg) == []


def test_jl007_intentional_drain_suppressed_inline():
    findings = lint("""
        import numpy as np

        def fetch_to_host(arr):
            return np.asarray(arr)  # jaxlint: disable=JL007 -- the drain
    """, **HOT)
    assert findings == []


def test_jl007_block_until_ready_not_flagged():
    # a sync without a transfer is legitimate hot-path code (warmup, timing)
    findings = lint("""
        import jax

        def warm(arr):
            jax.block_until_ready(arr)
    """, **HOT)
    assert findings == []


def _repo_config():
    """The SHIPPED .jaxlint.json (not a fixture) — these tests pin that the
    training engine is actually policed in the committed config."""
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(root, ".jaxlint.json")
    if not os.path.isfile(path):
        pytest.skip("source tree layout not available")
    with open(path) as f:
        return json.load(f)


def test_jl007_shipped_config_covers_training_engine():
    raw = _repo_config()
    hot = raw["rules"]["JL007"]["options"]["hot_paths"]
    assert "deepspeed_tpu/runtime/engine.py" in hot
    assert any("inference/v2" in p for p in hot)
    # the offloaded optimizer pipeline is a hot path too: a stray blocking
    # fetch there re-serialises the fetch/step/upload overlap
    assert "deepspeed_tpu/runtime/zero/offload.py" in hot
    # the rolling-checkpoint snapshot runs ON the step loop's critical path:
    # every device fetch there must route through the policed drain point
    assert "deepspeed_tpu/checkpoint/rolling.py" in hot


def test_jl007_offload_module_fetch_flagged():
    # a dtype-less np.array/np.asarray in the offload hot path (e.g. the
    # swap-buffer copy-out) must fire under the SHIPPED hot_paths
    raw = _repo_config()
    cfg = LintConfig(rules={"JL007": RuleSettings(
        options=raw["rules"]["JL007"]["options"])})
    src = textwrap.dedent("""
        import numpy as np

        def group_step(views, updated, name):
            updated[name] = np.array(views[name])
    """)
    findings = lint_text(src, path="deepspeed_tpu/runtime/zero/offload.py",
                         config=cfg)
    assert rules_of(findings) == ["JL007"]


def test_jl007_offload_module_discipline_clean():
    # the module's actual discipline: host-only numpy with explicit dtypes
    # (the engine owns the single drain point; offload.py never sees a
    # device array)
    raw = _repo_config()
    cfg = LintConfig(rules={"JL007": RuleSettings(
        options=raw["rules"]["JL007"]["options"])})
    src = textwrap.dedent("""
        import numpy as np

        def step_leaf(grads, name, grad_scale):
            g = np.ascontiguousarray(grads[name].reshape(-1), np.float32)
            if grad_scale != 1.0:
                g = g * np.float32(grad_scale)
            return g

        def copy_out(views, name):
            return np.array(views[name], np.float32)
    """)
    findings = lint_text(src, path="deepspeed_tpu/runtime/zero/offload.py",
                         config=cfg)
    assert findings == []


def test_jl007_training_engine_path_flagged():
    # a stray blocking fetch added to the engine module must fire under the
    # SHIPPED hot_paths (the PR-4 deferred-drain discipline)
    raw = _repo_config()
    cfg = LintConfig(rules={"JL007": RuleSettings(
        options=raw["rules"]["JL007"]["options"])})
    src = textwrap.dedent("""
        import numpy as np

        def _after_step(metrics):
            return float(np.asarray(metrics["loss"]))
    """)
    findings = lint_text(src, path="deepspeed_tpu/runtime/engine.py",
                         config=cfg)
    assert rules_of(findings) == ["JL007"]


def test_jl007_training_engine_drain_pattern_clean():
    # the engine's actual discipline: ONE suppressed drain point, dtype'd
    # host conversions everywhere else
    raw = _repo_config()
    cfg = LintConfig(rules={"JL007": RuleSettings(
        options=raw["rules"]["JL007"]["options"])})
    src = textwrap.dedent("""
        import jax
        import numpy as np

        def fetch_to_host(tree):
            return jax.device_get(tree)  # jaxlint: disable=JL007 -- the intentional drain

        def _emit_metrics(metrics):
            vals = fetch_to_host(metrics)
            return float(vals["loss"])

        def _host_master_flat(leaves):
            return np.concatenate([np.asarray(v, np.float32) for v in leaves])
    """)
    findings = lint_text(src, path="deepspeed_tpu/runtime/engine.py",
                         config=cfg)
    assert findings == []


# --------------------------------------------------------------------------- #
# suppressions / baseline / config / CLI
# --------------------------------------------------------------------------- #

def test_line_suppression():
    findings = lint("""
        import jax

        KEY = jax.random.PRNGKey(0)  # jaxlint: disable=JL002
    """)
    assert findings == []


def test_line_suppression_wrong_rule_does_not_hide():
    findings = lint("""
        import jax

        KEY = jax.random.PRNGKey(0)  # jaxlint: disable=JL001
    """)
    assert rules_of(findings) == ["JL002"]


def test_file_suppression():
    findings = lint("""
        # jaxlint: disable-file=JL002
        import jax
        KEY = jax.random.PRNGKey(0)
        OTHER = jax.random.PRNGKey(1)
    """)
    assert findings == []


def test_docstring_mention_is_not_a_suppression():
    # documenting the directive in a docstring must not install it
    findings = lint('''
        """Docs: write ``# jaxlint: disable-file=JL002`` to suppress a file."""
        import jax
        KEY = jax.random.PRNGKey(0)
    ''')
    assert rules_of(findings) == ["JL002"]


def test_disable_all_on_line():
    findings = lint("""
        import jax
        KEY = jax.random.PRNGKey(7)  # jaxlint: disable=all
    """)
    assert findings == []


def test_rule_disabled_via_config():
    src = "import jax\nKEY = jax.random.PRNGKey(0)\n"
    cfg = LintConfig(rules={"JL002": RuleSettings(enabled=False)})
    assert lint_text(src, path="pkg/mod.py", config=cfg) == []


def test_baseline_roundtrip(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import jax\nKEY = jax.random.PRNGKey(0)\n")
    findings = lint_text(bad.read_text(), path=str(bad))
    assert rules_of(findings) == ["JL002"]

    bl = tmp_path / "baseline.json"
    write_baseline(str(bl), findings, root=str(tmp_path))
    loaded = load_baseline(str(bl))
    assert sum(loaded.values()) == 1

    new, grandfathered = apply_baseline(findings, loaded, root=str(tmp_path))
    assert new == [] and rules_of(grandfathered) == ["JL002"]

    # a second identical finding is NOT covered by a count-1 baseline
    new2, _ = apply_baseline(findings * 2, loaded, root=str(tmp_path))
    assert len(new2) == 1


def test_cli_end_to_end(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("import jax\n\ndef f():\n    return jax.random.PRNGKey(0)\n")
    good = tmp_path / "good.py"
    good.write_text("def f(rng):\n    return rng\n")

    assert jaxlint_main([str(good), "--no-config"]) == 0
    assert jaxlint_main([str(bad), "--no-config"]) == 1
    out = capsys.readouterr().out
    assert "JL002" in out

    # --select an unrelated rule: clean
    assert jaxlint_main([str(bad), "--no-config", "--select", "JL001"]) == 0
    # --disable the firing rule: clean
    assert jaxlint_main([str(bad), "--no-config", "--disable", "JL002"]) == 0

    # baseline workflow: write, then rerun green
    bl = tmp_path / "bl.json"
    assert jaxlint_main([str(bad), "--no-config", "--baseline", str(bl),
                         "--write-baseline"]) == 0
    assert jaxlint_main([str(bad), "--no-config", "--baseline", str(bl)]) == 0

    # json format
    capsys.readouterr()  # flush text-mode output from the runs above
    assert jaxlint_main([str(bad), "--no-config", "--format", "json"]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload and payload[0]["rule"] == "JL002"


def test_cli_missing_path_is_usage_error(tmp_path):
    assert jaxlint_main([str(tmp_path / "nope.py"), "--no-config"]) == 2


def test_cli_unknown_rule_id_is_usage_error(tmp_path, capsys):
    # a typo'd --select must NOT silently disable every rule and exit green
    ok = tmp_path / "ok.py"
    ok.write_text("x = 1\n")
    assert jaxlint_main([str(ok), "--no-config", "--select", "JL999"]) == 2
    assert jaxlint_main([str(ok), "--no-config", "--disable", "JL13"]) == 2
    assert "unknown rule id" in capsys.readouterr().err


def test_cli_parse_error_reported(tmp_path, capsys):
    broken = tmp_path / "broken.py"
    broken.write_text("def f(:\n")
    assert jaxlint_main([str(broken), "--no-config"]) == 1
    assert "JL000" in capsys.readouterr().out


def test_parse_errors_are_never_baselined(tmp_path):
    # an unparseable file gets no rule coverage; grandfathering it would
    # exempt it from the linter forever
    broken = tmp_path / "broken.py"
    broken.write_text("def f(:\n")
    bl = tmp_path / "bl.json"
    assert jaxlint_main([str(broken), "--no-config", "--baseline", str(bl),
                         "--write-baseline"]) == 1
    assert load_baseline(str(bl)) == {}
    # and the rerun still fails
    assert jaxlint_main([str(broken), "--no-config", "--baseline", str(bl)]) == 1


def test_config_load_and_discovery(tmp_path):
    (tmp_path / ".jaxlint.json").write_text(json.dumps({
        "exclude": ["vendored/"],
        "baseline": "bl.json",
        "rules": {"JL001": {"enabled": False},
                  "JL005": {"options": {"known_axes": ["data"]}}},
    }))
    sub = tmp_path / "pkg"
    sub.mkdir()
    from deepspeed_tpu.tools.jaxlint.config import find_config
    found = find_config(str(sub))
    assert found == str(tmp_path / ".jaxlint.json")
    cfg = LintConfig.load(found)
    assert not cfg.rule("JL001").enabled
    assert cfg.rule("JL005").options["known_axes"] == ["data"]
    assert cfg.baseline_path() == str(tmp_path / "bl.json")


def test_repo_tree_is_clean():
    """The shipped tree lints clean under the shipped config — the CI gate."""
    import os
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    pkg = os.path.join(root, "deepspeed_tpu")
    cfg_path = os.path.join(root, ".jaxlint.json")
    if not os.path.isdir(pkg) or not os.path.isfile(cfg_path):
        pytest.skip("source tree layout not available")
    assert jaxlint_main([pkg, "--config", cfg_path]) == 0


# --------------------------------------------------------------------------- #
# JL008 — tracer span enclosing a blocking fetch
# --------------------------------------------------------------------------- #

_JL008_OPTS = {"JL008": {"hot_paths": ["pkg/"]}}


def test_jl008_flags_device_get_inside_span():
    findings = lint("""
        import jax
        from deepspeed_tpu.monitor.trace import tracer

        def drain(arr):
            with tracer.span("train/step/drain"):
                vals = jax.device_get(arr)
            return vals
        """, **_JL008_OPTS)
    assert "JL008" in rules_of(findings)


def test_jl008_flags_bare_asarray_and_item_inside_span():
    findings = lint("""
        import numpy as np
        from deepspeed_tpu.monitor.trace import tracer

        def leak(arr, metrics):
            with tracer.span("serve/decode/step", step=1):
                row = np.asarray(arr)
                loss = metrics.item()
            return row, loss
        """, **_JL008_OPTS)
    assert rules_of(findings).count("JL008") == 2


def test_jl008_policed_drain_inside_span_is_clean():
    # attributing the sanctioned drain's cost is exactly what spans are FOR
    findings = lint("""
        from deepspeed_tpu.monitor.trace import tracer
        from pkg.engine import fetch_to_host

        def drain(tree):
            with tracer.span("train/drain"):
                vals = fetch_to_host(tree)
            return vals
        """, **_JL008_OPTS)
    assert "JL008" not in rules_of(findings)


def test_jl008_host_conversions_and_fetch_outside_span_clean():
    findings = lint("""
        import jax
        import numpy as np
        from deepspeed_tpu.monitor.trace import tracer

        def stage(batch, arr):
            host = np.asarray(batch, np.float32)   # dtype'd: host-side
            with tracer.span("train/prefetch/stage"):
                out = host * 2
            vals = jax.device_get(arr)             # outside the span
            return out, vals
        """, **_JL008_OPTS)
    assert "JL008" not in rules_of(findings)


def test_jl008_nested_function_inside_span_not_enclosed():
    # work SUBMITTED from inside a span isn't synchronously enclosed by it
    findings = lint("""
        import jax
        from deepspeed_tpu.monitor.trace import tracer

        def schedule(pool, arr):
            with tracer.span("ckpt/submit"):
                def write():
                    return jax.device_get(arr)
                fut = pool.submit(write)
            return fut
        """, **_JL008_OPTS)
    assert "JL008" not in rules_of(findings)


def test_jl008_inert_without_hot_path_config():
    findings = lint("""
        import jax
        from deepspeed_tpu.monitor.trace import tracer

        def drain(arr):
            with tracer.span("x"):
                return jax.device_get(arr)
        """)
    assert "JL008" not in rules_of(findings)


def test_jl008_shipped_config_covers_traced_modules():
    raw = _repo_config()
    opts = raw["rules"]["JL008"]["options"]
    hot = opts["hot_paths"]
    # every JL007 hot path stays policed under spans too...
    for p in raw["rules"]["JL007"]["options"]["hot_paths"]:
        assert p in hot
    # ...plus the span-instrumented lanes JL007 does not police
    assert "deepspeed_tpu/runtime/data_pipeline.py" in hot
    assert any("swap_tensor" in p for p in hot)
    assert opts["drain_calls"] == ["fetch_to_host"]


def test_jl007_serving_frontend_path_policed():
    """The serving subsystem (inference/v2/serving/) is hot-path policed by
    the SHIPPED config — a stray blocking fetch in the frontend's token
    callback fires; its actual discipline (host ints, explicit dtypes,
    engine-owned drain) is clean."""
    raw = _repo_config()
    hot = raw["rules"]["JL007"]["options"]["hot_paths"]
    assert "deepspeed_tpu/inference/v2/serving/" in hot
    assert "deepspeed_tpu/inference/v2/serving/" in \
        raw["rules"]["JL008"]["options"]["hot_paths"]
    cfg = LintConfig(rules={"JL007": RuleSettings(
        options=raw["rules"]["JL007"]["options"])})
    src = textwrap.dedent("""
        import numpy as np

        def _on_tokens(self, j, uids, row):
            return np.asarray(row).tolist()
    """)
    findings = lint_text(
        src, path="deepspeed_tpu/inference/v2/serving/frontend.py",
        config=cfg)
    assert rules_of(findings) == ["JL007", "JL007"]
    clean = textwrap.dedent("""
        import numpy as np

        def _on_tokens(self, j, uids, row):
            out = []
            for i, u in enumerate(uids):
                out.append(int(row[i]))
            return np.asarray(out, np.int32)
    """)
    assert lint_text(
        clean, path="deepspeed_tpu/inference/v2/serving/admission.py",
        config=cfg) == []


def test_jl007_router_cluster_paths_policed():
    """The multi-replica router/cluster modules (serving/router.py +
    serving/cluster.py) are hot-path policed by the SHIPPED config via the
    serving/ prefix — a stray blocking fetch of handoff pages on the
    routing path fires; the modules' actual discipline (dtype'd host
    conversions, the engine-owned export/import drains) is clean."""
    raw = _repo_config()
    for rule in ("JL007", "JL008"):
        hot = raw["rules"][rule]["options"]["hot_paths"]
        for mod in ("deepspeed_tpu/inference/v2/serving/router.py",
                    "deepspeed_tpu/inference/v2/serving/cluster.py"):
            assert any(p in mod for p in hot), (rule, mod)
    cfg = LintConfig(rules={"JL007": RuleSettings(
        options=raw["rules"]["JL007"]["options"])})
    src = textwrap.dedent("""
        import numpy as np

        def _prefill_and_handoff(self, live):
            pages = np.asarray(self.engine.kv.kv)
            return pages.tolist()
    """)
    findings = lint_text(
        src, path="deepspeed_tpu/inference/v2/serving/router.py",
        config=cfg)
    assert rules_of(findings) == ["JL007", "JL007"]


def test_jl007_health_module_policed():
    """The failover/health module (serving/health.py) is hot-path policed
    by the SHIPPED config via the serving/ prefix — a migration that
    blocking-fetched a dead replica's device pages on the monitor thread
    fires; the module's actual discipline (host dicts, sealed handles, the
    engine-owned export/import drains) is clean."""
    raw = _repo_config()
    for rule in ("JL007", "JL008"):
        hot = raw["rules"][rule]["options"]["hot_paths"]
        assert any(p in "deepspeed_tpu/inference/v2/serving/health.py"
                   for p in hot), rule
    cfg = LintConfig(rules={"JL007": RuleSettings(
        options=raw["rules"]["JL007"]["options"])})
    src = textwrap.dedent("""
        import numpy as np

        def _migrate_one(self, replica, fe, req, handoff):
            pages = np.asarray(replica.engine.kv.kv)
            return pages.tolist()
    """)
    findings = lint_text(
        src, path="deepspeed_tpu/inference/v2/serving/health.py",
        config=cfg)
    assert rules_of(findings) == ["JL007", "JL007"]
    clean = textwrap.dedent("""
        import numpy as np

        def _migrate_one(self, replica, fe, req, handoff):
            history = req._seal()
            pages, logits, nbytes = fe.offload.export_record(req.uid)
            return np.asarray(history, np.int32), pages, logits
    """)
    assert lint_text(
        clean, path="deepspeed_tpu/inference/v2/serving/health.py",
        config=cfg) == []


def test_jl007_spec_decode_path_policed():
    """The speculative-decoding subsystem (inference/v2/spec/) is hot-path
    policed by the SHIPPED config — a stray blocking fetch of the accept
    row fires; the pipeline's actual discipline (dtype'd host conversions,
    the engine-owned fetch_to_host drain) is clean."""
    raw = _repo_config()
    hot = raw["rules"]["JL007"]["options"]["hot_paths"]
    assert "deepspeed_tpu/inference/v2/spec/" in hot
    assert "deepspeed_tpu/inference/v2/spec/" in \
        raw["rules"]["JL008"]["options"]["hot_paths"]
    cfg = LintConfig(rules={"JL007": RuleSettings(
        options=raw["rules"]["JL007"]["options"])})
    src = textwrap.dedent("""
        import numpy as np

        def run_step(accept_row):
            row = np.asarray(accept_row)
            return row[0].tolist()
    """)
    findings = lint_text(
        src, path="deepspeed_tpu/inference/v2/spec/pipeline.py", config=cfg)
    assert rules_of(findings) == ["JL007", "JL007"]
    clean = textwrap.dedent("""
        import numpy as np
        from deepspeed_tpu.inference.v2.engine_v2 import fetch_to_host

        def run_step(accept_row, hist):
            row = fetch_to_host(accept_row)
            draft = np.asarray(hist, np.int32)
            return row, draft
    """)
    assert lint_text(
        clean, path="deepspeed_tpu/inference/v2/spec/pipeline.py",
        config=cfg) == []


def test_monitor_paths_policed_by_shipped_config():
    """The monitor package (the tracer, the stats classes, and the live
    telemetry exporter ``monitor/export.py``) is hot-path policed: the
    event/export path runs beside the serving loops, so a stray device
    fetch there is a serving stall wearing a telemetry hat."""
    raw = _repo_config()
    for rule in ("JL007", "JL008"):
        hot = raw["rules"][rule]["options"]["hot_paths"]
        assert "deepspeed_tpu/monitor/" in hot, rule


def test_jl007_monitor_export_event_path_policed():
    """A blocking fetch smuggled onto the exporter's ``write_events`` path
    (materialising a device value 'for the snapshot') fires under the
    SHIPPED hot_paths; the module's actual discipline — host floats only,
    rendering deferred to scrape time — is clean."""
    raw = _repo_config()
    cfg = LintConfig(rules={"JL007": RuleSettings(
        options=raw["rules"]["JL007"]["options"])})
    src = textwrap.dedent("""
        import numpy as np

        def write_events(self, event_list):
            for name, value, step in event_list:
                self._values[name] = (float(np.asarray(value)), int(step))
    """)
    findings = lint_text(src, path="deepspeed_tpu/monitor/export.py",
                         config=cfg)
    assert rules_of(findings) == ["JL007"]
    clean = textwrap.dedent("""
        def write_events(self, event_list):
            for name, value, step in event_list:
                self._values[name] = (float(value), int(step))

        def render(self):
            lines = []
            for name, (value, step) in sorted(self._values.items()):
                lines.append(f"{name} {value!r}")
            return "\\n".join(lines)
    """)
    assert lint_text(clean, path="deepspeed_tpu/monitor/export.py",
                     config=cfg) == []


def test_jl008_monitor_stats_span_fetch_policed():
    """A span wrapped around a device drain in the stats/rollup path (the
    stats-equals-spans surfaces feeding serve/slo/*) fires under the
    SHIPPED JL008 options; perf-stamp-only rollups are clean."""
    raw = _repo_config()
    cfg = LintConfig(rules={"JL008": RuleSettings(
        options=raw["rules"]["JL008"]["options"])})
    src = textwrap.dedent("""
        import jax
        from deepspeed_tpu.monitor.trace import tracer

        def events(self, step):
            with tracer.span("serve/slo/rollup"):
                return jax.device_get(self.rollup)
    """)
    findings = lint_text(src, path="deepspeed_tpu/monitor/serving.py",
                         config=cfg)
    assert "JL008" in rules_of(findings)
    clean = textwrap.dedent("""
        import time
        from deepspeed_tpu.monitor.trace import tracer

        def record_slo_miss(self, cls, phase, consistent):
            t0 = time.perf_counter()
            with tracer.span("serve/slo/record"):
                self.slo_missed += 1
                self.by_phase[phase] = self.by_phase.get(phase, 0) + 1
            return time.perf_counter() - t0
    """)
    assert "JL008" not in rules_of(lint_text(
        clean, path="deepspeed_tpu/monitor/serving.py", config=cfg))


def test_shipped_baseline_stays_empty():
    """The ratchet: every hot-path expansion (this PR: monitor/) must land
    with the shipped tree CLEAN under it, never by growing the baseline."""
    import os
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(root, ".jaxlint-baseline.json")
    if not os.path.isfile(path):
        pytest.skip("source tree layout not available")
    with open(path) as f:
        baseline = json.load(f)
    assert baseline.get("entries") == {}


def test_jl007_zero3_prefetch_path_policed():
    """The ZeRO-3 collective schedule (runtime/zero/prefetch.py) is hot-path
    policed by the SHIPPED config: a stray blocking fetch while draining the
    stamp ledger re-serialises the very gather/compute overlap the schedule
    exists to create."""
    raw = _repo_config()
    assert "deepspeed_tpu/runtime/zero/prefetch.py" in \
        raw["rules"]["JL007"]["options"]["hot_paths"]
    assert "deepspeed_tpu/runtime/zero/prefetch.py" in \
        raw["rules"]["JL008"]["options"]["hot_paths"]
    cfg = LintConfig(rules={"JL007": RuleSettings(
        options=raw["rules"]["JL007"]["options"])})
    src = textwrap.dedent("""
        import numpy as np

        def drain(ledger):
            return [np.asarray(t) for t in ledger]
    """)
    findings = lint_text(src, path="deepspeed_tpu/runtime/zero/prefetch.py",
                         config=cfg)
    assert rules_of(findings) == ["JL007"]


def test_jl007_zero3_prefetch_discipline_clean():
    # the module's actual discipline: stamps are host floats recorded by
    # debug-callback taps; the drain aggregates them without ever touching
    # a device array
    raw = _repo_config()
    cfg = LintConfig(rules={"JL007": RuleSettings(
        options=raw["rules"]["JL007"]["options"])})
    src = textwrap.dedent("""
        import time

        _LEDGER = []

        def _record(wave, kind, _probe):
            _LEDGER.append((wave, kind, time.perf_counter()))

        def drain(tracer, plan):
            stamps = list(_LEDGER)
            for wave, kind, t in stamps:
                tracer.add("train/zero3/gather", t, t,
                           lane="train/zero3/gather", wave=wave)
            return len(stamps)
    """)
    findings = lint_text(src, path="deepspeed_tpu/runtime/zero/prefetch.py",
                         config=cfg)
    assert findings == []


def test_jl008_zero3_prefetch_span_policed():
    """Under the SHIPPED config a device fetch inside a train/zero3 span
    fires (the span would time the fetch, not the collective); the drain's
    actual shape — host-float spans emitted after the fact — is clean."""
    raw = _repo_config()
    cfg = LintConfig(rules={"JL008": RuleSettings(
        options=raw["rules"]["JL008"]["options"])})
    src = textwrap.dedent("""
        import jax
        from deepspeed_tpu.monitor.trace import tracer

        def emit(probe):
            with tracer.span("train/zero3/gather"):
                return jax.device_get(probe)
    """)
    findings = lint_text(src, path="deepspeed_tpu/runtime/zero/prefetch.py",
                         config=cfg)
    assert "JL008" in rules_of(findings)
    clean = textwrap.dedent("""
        from deepspeed_tpu.monitor.trace import tracer

        def emit(segments):
            for per in segments:
                with tracer.span("train/zero3/drain"):
                    for (wave, kind), t in per.items():
                        tracer.add("train/zero3/gather", t, t,
                                   lane="train/zero3/gather", wave=wave)
    """)
    assert "JL008" not in rules_of(lint_text(
        clean, path="deepspeed_tpu/runtime/zero/prefetch.py", config=cfg))


def test_jl007_splitk_module_policed():
    """The split-K dispatchers (ops/pallas/paged_splitk.py) run inside
    every warmed decode program — the SHIPPED config hot-path polices the
    module: a stray blocking fetch (e.g. a debug drain of the partials)
    fires; its actual discipline (pure jnp tracing code, no host
    conversions) is clean."""
    raw = _repo_config()
    hot = raw["rules"]["JL007"]["options"]["hot_paths"]
    assert "deepspeed_tpu/ops/pallas/paged_splitk.py" in hot
    cfg = LintConfig(rules={"JL007": RuleSettings(
        options=raw["rules"]["JL007"]["options"])})
    src = textwrap.dedent("""
        import numpy as np

        def merge_debug(out_p, lse_p):
            return np.asarray(lse_p).max()
    """)
    findings = lint_text(src,
                         path="deepspeed_tpu/ops/pallas/paged_splitk.py",
                         config=cfg)
    assert rules_of(findings) == ["JL007"]
    clean = textwrap.dedent("""
        import jax.numpy as jnp

        def merge(out_p, lse_p):
            m = jnp.max(lse_p, axis=0)
            w = jnp.exp(lse_p - m[None])
            num = jnp.einsum("sbh,sbhd->bhd", w, out_p)
            return num / jnp.sum(w, axis=0)[..., None]
    """)
    assert lint_text(clean,
                     path="deepspeed_tpu/ops/pallas/paged_splitk.py",
                     config=cfg) == []


def test_jl008_splitk_module_span_policed():
    """A serve/attn span must never enclose a blocking fetch — the rung
    selection span times a host scan, and a device drain inside it would
    bill kernel wait to the selector. The module's clean shape (span around
    host-only arithmetic) passes."""
    raw = _repo_config()
    assert "deepspeed_tpu/ops/pallas/paged_splitk.py" in \
        raw["rules"]["JL008"]["options"]["hot_paths"]
    cfg = LintConfig(rules={"JL008": RuleSettings(
        options=raw["rules"]["JL008"]["options"])})
    src = textwrap.dedent("""
        import jax
        from deepspeed_tpu.monitor.trace import tracer

        def pick_rung(partials):
            with tracer.span("serve/attn/select"):
                return jax.device_get(partials)
    """)
    findings = lint_text(src,
                         path="deepspeed_tpu/ops/pallas/paged_splitk.py",
                         config=cfg)
    assert "JL008" in rules_of(findings)
    clean = textwrap.dedent("""
        from deepspeed_tpu.monitor.trace import tracer

        def pick_rung(live_ctx, min_ctx, top):
            with tracer.span("serve/attn/select"):
                want = max(1, live_ctx // min_ctx)
                return min(top, 1 << (want.bit_length() - 1))
    """)
    assert "JL008" not in rules_of(lint_text(
        clean, path="deepspeed_tpu/ops/pallas/paged_splitk.py", config=cfg))
