"""The documents and the package's own docstrings name files of this tree.

Every back-quoted repo path in ``README.md``, in each ``docs/*.md`` (the dated
``ROUND*_NOTES.md`` aside) and anywhere in the package's sources — a path
under ``benchmarks/``, ``scripts/``, ``deepspeed_tpu/``, ``chipbench/``,
``tests/``, ``docs/`` or ``examples/``, or a record-like ``*.json`` / ``*.md``
/ ``*.jsonl`` name at the root — must be a file (or directory) that exists,
and a ``tests/...py::name`` must name a test defined there. A document that
cites a deleted harness, a deleted record or a renamed test fails here.
"""

import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
_DOCS = [ROOT / "README.md"] + sorted(
    p for p in (ROOT / "docs").glob("*.md")
    if not re.fullmatch(r"ROUND\d+_NOTES\.md", p.name))

_TOP = ("benchmarks", "scripts", "deepspeed_tpu", "chipbench", "tests",
        "docs", "examples")
#: a path under one of the tree's directories, optionally ``::test_name``
_PATH = re.compile(
    r"(?<![\w./-])((?:%s)/[\w./-]*\w/?)(?:::(\w+))?" % "|".join(_TOP))
#: a record-like name at the root: BASELINE.json, PERF.md, PERF_LEDGER.jsonl
_ROOT_NAME = re.compile(r"(?<![\w./-])([A-Z][\w-]*\.(?:jsonl|json|md))\b")
#: what a reader would take for a placeholder, not a path
_PLACEHOLDER = re.compile(r"[<>*{}$…]|\.\.\.")


def _spans(text, markdown):
    """The pieces of ``text`` that claim to name something: fenced blocks,
    back-quoted spans and link targets of a markdown document; all of a
    source file."""
    if not markdown:
        return [text]
    fenced = re.findall(r"```.*?```", text, flags=re.S)
    inline = re.sub(r"```.*?```", " ", text, flags=re.S)
    return fenced + re.findall(r"`([^`\n]+)`", inline) \
        + re.findall(r"\]\(([^)#\s]+)", inline)


def _missing(path, markdown):
    text = path.read_text()
    missing = []
    for span in _spans(text, markdown):
        for word in span.split():
            if _PLACEHOLDER.search(word):
                continue
            for rel, test in _PATH.findall(word):
                target = ROOT / rel
                if rel.endswith("/"):
                    ok = target.is_dir()
                elif "." not in rel.rsplit("/", 1)[-1]:
                    continue                 # "tests/stats": prose, not a path
                else:
                    # ``scripts/lint.sh.`` at a sentence's end, or a module
                    # named without its suffix
                    ok = target.exists() or (ROOT / rel.rstrip(".")).exists()
                if ok and test:
                    ok = re.search(r"def %s\b" % re.escape(test),
                                   target.read_text()) is not None
                if not ok:
                    missing.append(rel + (f"::{test}" if test else ""))
            for name in _ROOT_NAME.findall(word):
                if not ((ROOT / name).exists()
                        or (path.parent / name).exists()
                        or (ROOT / "docs" / name).exists()):
                    missing.append(name)
    return sorted(set(missing))


@pytest.mark.parametrize("doc", _DOCS,
                         ids=[str(p.relative_to(ROOT)) for p in _DOCS])
def test_document_names_files_that_exist(doc):
    assert _missing(doc, markdown=True) == []


def test_package_sources_name_files_that_exist():
    missing = {}
    for src in sorted((ROOT / "deepspeed_tpu").rglob("*.py")):
        bad = _missing(src, markdown=False)
        if bad:
            missing[str(src.relative_to(ROOT))] = bad
    assert missing == {}
