"""The layering of the serving programs, held by reading source: which
module may import which (docs/SERVING.md "Where a family lives").

``models`` -> ``adapters`` -> ``model_spec`` <- ``ragged_model`` /
``ragged_mla`` <- ``engine_v2`` <- ``scheduler`` / ``pipeline`` <-
``serving/``. No engine is built and no program compiled here.
"""

import ast
import importlib
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PKG = os.path.join(ROOT, "deepspeed_tpu")
V2 = os.path.join(PKG, "inference", "v2")
V2_MOD = "deepspeed_tpu.inference.v2"


def imported(path):
    """Every module a file imports, at any depth of nesting, as dotted
    names: ``from a.b import c`` gives ``a.b`` and ``a.b.c``."""
    with open(path) as f:
        tree = ast.parse(f.read())
    pkg = os.path.relpath(os.path.dirname(path), ROOT).split(os.sep)
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:      # relative: resolve against the file's package
                up = pkg[:len(pkg) - node.level + 1]
                base = ".".join(up + ([base] if base else []))
            names.add(base)
            names.update(f"{base}.{a.name}" for a in node.names)
    return names


def python_files(top):
    return sorted(os.path.join(d, f) for d, _, fs in os.walk(top)
                  for f in fs if f.endswith(".py"))


ADAPTER_MODULES = sorted(f[:-3] for f in os.listdir(
    os.path.join(V2, "adapters")) if f.endswith(".py"))


@pytest.mark.parametrize("module", ADAPTER_MODULES)
def test_an_adapter_imports_no_program_and_no_engine(module):
    """An adapter turns weights into ``(spec, stacks)``; a line added to one
    must not be able to move a traced line."""
    names = imported(os.path.join(V2, "adapters", module + ".py"))
    above = {f"{V2_MOD}.{m}" for m in
             ("ragged_model", "ragged_mla", "engine_v2", "scheduler")}
    assert not names & above, sorted(names & above)


def test_there_is_a_module_a_family_or_lineage():
    assert set(ADAPTER_MODULES) >= {
        "llama", "gpt2", "decoder", "afmoe", "jamba", "joyai", "granite",
        "nemotron_h", "qwen3_next", "zaya", "brumby"}


def test_the_spec_is_a_leaf():
    """``model_spec`` imports nothing of ``inference/v2``, and of the kernels
    only the width of a latent row."""
    ours = {n for n in imported(os.path.join(V2, "model_spec.py"))
            if n.startswith("deepspeed_tpu")}
    assert ours == {"deepspeed_tpu.ops.pallas.mla_attention",
                    "deepspeed_tpu.ops.pallas.mla_attention.latent_row_width"}


def test_no_kernel_imports_the_serving_layers():
    up = {(os.path.relpath(p, ROOT), n)
          for p in python_files(os.path.join(PKG, "ops"))
          for n in imported(p) if n.startswith("deepspeed_tpu.inference")}
    assert not up, sorted(up)


def test_the_program_builders_do_not_import_the_scheduler():
    for module in ("ragged_model", "ragged_mla"):
        names = imported(os.path.join(V2, module + ".py"))
        assert f"{V2_MOD}.scheduler" not in names, module
        assert f"{V2_MOD}.engine_v2" not in names, module


def test_every_adapter_lives_under_adapters():
    adapters = importlib.import_module(f"{V2_MOD}.adapters")
    homes = {family: fn.__module__ for family, fn in adapters.ADAPTERS.items()}
    assert all(m.startswith(f"{V2_MOD}.adapters.") for m in homes.values()), \
        homes


def test_every_program_builder_lives_with_the_programs():
    found = {}
    for path in python_files(V2):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in tree.body:
            if isinstance(node, ast.FunctionDef) \
                    and node.name.lstrip("_").startswith("build_"):
                found.setdefault(os.path.relpath(path, V2), []).append(
                    node.name)
    assert set(found) == {"ragged_model.py", "ragged_mla.py"}, found


#: what ``ragged_model`` re-exports for the benchmark's accepted files
#: (``chipbench/``, ``tests/chipbench/``), with the module that holds each
REEXPORTED = [
    ("ADAPTERS", "adapters"),
    ("adapt_glm_dsa", "adapters.joyai"),
    ("adapt_zaya", "adapters.zaya"),
    ("zaya_channel_order", "adapters.zaya"),
    ("describe_layer_kinds", "model_spec"),
    ("layer_runs", "model_spec"),
    ("num_page_layers", "model_spec"),
    ("num_state_layers", "model_spec"),
]


@pytest.mark.parametrize("name,home", REEXPORTED)
def test_a_reexported_name_is_the_one_its_module_holds(name, home):
    ragged_model = importlib.import_module(f"{V2_MOD}.ragged_model")
    module = importlib.import_module(f"{V2_MOD}.{home}")
    assert getattr(ragged_model, name) is getattr(module, name)


def read_through_ragged_model(path):
    """The names a file reads through ``ragged_model``: imported from it, or
    taken as attributes of it under whatever name the file gave it."""
    with open(path) as f:
        tree = ast.parse(f.read())
    aliases, names = set(), set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.ImportFrom):
            continue
        if node.module == f"{V2_MOD}.ragged_model":
            names.update(a.name for a in node.names)
        elif node.module == V2_MOD:
            aliases.update(a.asname or a.name for a in node.names
                           if a.name == "ragged_model")
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) \
                and isinstance(node.value, ast.Name) \
                and node.value.id in aliases:
            names.add(node.attr)
    return names


def test_the_benchmark_reads_what_resolves_and_the_block_holds_no_more():
    """The benchmark's imports of ``ragged_model`` sit inside functions, so
    importing its files proves nothing: every name they read resolves, the
    moved ones among them are exactly the re-exported block (ROADMAP D22),
    and nothing else in the repo reads a moved name through it."""
    ragged_model = importlib.import_module(f"{V2_MOD}.ragged_model")
    with open(os.path.join(V2, "ragged_model.py")) as f:
        own = {n.name for n in ast.parse(f.read()).body
               if isinstance(n, (ast.FunctionDef, ast.ClassDef))}
    read = set()
    for top in ("chipbench", os.path.join("tests", "chipbench")):
        for path in python_files(os.path.join(ROOT, top)):
            read |= read_through_ragged_model(path)
    assert read, "the scan found no reader"
    missing = sorted(n for n in read if not hasattr(ragged_model, n))
    assert not missing, missing
    assert read - own == {name for name, _ in REEXPORTED}
    moved = {name for name, _ in REEXPORTED}
    others = {}
    for top in ("deepspeed_tpu", "scripts", "examples", "chip_smoke.py",
                os.path.join("tests", "unit")):
        path = os.path.join(ROOT, top)
        for p in ([path] if path.endswith(".py") else python_files(path)):
            if os.path.samefile(p, os.path.join(V2, "ragged_model.py")):
                continue
            hit = read_through_ragged_model(p) & moved
            if hit:
                others[os.path.relpath(p, ROOT)] = sorted(hit)
    assert not others, others
