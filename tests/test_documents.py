"""The documents name only what exists.

A document that sends its reader to a script, a test or a function that is
not in the checkout teaches a system that is not there.  Every repo path a
held document writes must exist, and where it writes ``path.py::name``, a
``def``, ``class`` or assignment of ``name`` must be in that file.  The same
rule holds for the comments and docstrings of the library, and PERF.md must
speak of every cell and metric the benchmark declares.

Convention that keeps history tellable: a file that no longer exists is
spoken of by its bare name (``old_tool.py``), never by a path.

Not held: CHANGES.md, SURVEY.md, ADVICE.md, ISSUE.md (history, or another
tree's paths) and ROADMAP.md (rewritten between PRs by a session that runs
no tests, and it may name files yet to be written).
"""

import ast
import glob
import io
import json
import os
import re
import tokenize

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = ["README.md", "PERF.md", "BASELINE.md",
             ".claude/skills/verify/SKILL.md",
             *sorted(os.path.relpath(p, REPO)
                     for p in glob.glob(os.path.join(REPO, "docs", "*.md")))]

# A repo path as prose writes it; `<name>` and `{a,b}` placeholders do not
# match, which is wanted.  A trailing `::name` names something in the file.
REFERENCE = re.compile(
    r"(?<![\w/.\-])"
    r"((?:tpudp|tools|benchmarks|examples|perf|tests|docs)/[\w/.\-]*\w"
    r"\.(?:py|md|json|jsonl|sh)|bench\.py|chip_smoke\.py)"
    r"(?:::(\w+))?")


def dangling(text: str) -> list[str]:
    """The references of ``text`` that nothing in the checkout answers."""
    missing = []
    for path, name in sorted(set(REFERENCE.findall(text))):
        full = os.path.join(REPO, path)
        if not os.path.exists(full):
            missing.append(path)
        # a name ending in `_` is the head of a wrapped line, not a name
        elif name and not name.endswith("_") and path.endswith(".py"):
            with open(full, encoding="utf-8") as f:
                source = f.read()
            if not re.search(
                    rf"^\s*(?:(?:async\s+)?def\s+{name}\s*\(|class\s+{name}"
                    rf"\s*[(:]|{name}\s*(?::[^=\n]+)?=(?!=))", source, re.M):
                missing.append(f"{path}::{name}")
    return missing


def test_the_rule_sees_a_missing_file_and_a_missing_name():
    assert dangling("see `tools/no_such_tool.py` and "
                    "tests/test_documents.py::no_such_test, then "
                    "tests/test_documents.py::dangling and docs/<name>.md"
                    ) == ["tests/test_documents.py::no_such_test",
                          "tools/no_such_tool.py"]


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_names_only_what_exists(doc):
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        missing = dangling(f.read())
    assert not missing, (
        f"{doc} names what is not in the checkout: {missing} (speak of a "
        "file that is gone by its bare name, not by a path)")


def _comments_and_docstrings(source: str) -> str:
    parts = [tok.string for tok in tokenize.generate_tokens(
        io.StringIO(source).readline) if tok.type == tokenize.COMMENT]
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            parts.append(ast.get_docstring(node, clean=False) or "")
    return "\n".join(parts)


def test_library_comments_name_only_scripts_that_exist():
    missing = {}
    for path in glob.glob(os.path.join(REPO, "tpudp", "**", "*.py"),
                          recursive=True):
        with open(path, encoding="utf-8") as f:
            found = dangling(_comments_and_docstrings(f.read()))
        if found:
            missing[os.path.relpath(path, REPO)] = found
    assert not missing, missing


@pytest.fixture(scope="module")
def benchmark():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def perf_md():
    with open(os.path.join(REPO, "PERF.md"), encoding="utf-8") as f:
        return f.read()


def _unnamed(entries, perf_md):
    return [e["name"] for e in entries if f"`{e['name']}`" not in perf_md]


def test_perf_md_names_every_cell(benchmark, perf_md):
    assert not _unnamed(benchmark["workloads"], perf_md)


def test_perf_md_names_every_end_to_end_metric(benchmark, perf_md):
    assert not _unnamed(benchmark["end_to_end"], perf_md)


def test_perf_md_names_every_per_layer_metric(benchmark, perf_md):
    assert not _unnamed(benchmark["per_layer"], perf_md)
