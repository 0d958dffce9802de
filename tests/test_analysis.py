"""tpudp.analysis — linter rules, suppression machinery, CLI contract,
and the trace-stability auditor.

The rule contract is fixture-based (ISSUE 8 acceptance bar): every
shipped rule must FIRE on its seeded violation file
(tests/fixtures/analysis/bad_<rule>.py) and stay SILENT on the
corrected twin (good_<rule>.py) — no rule ships without a positive and
a negative case.  The tier-1 pins live in test_analysis_clean.py.
"""

import json
import os
import subprocess
import sys

import pytest

from tpudp.analysis import RULES_BY_NAME, lint_paths
from tpudp.analysis.cli import main as cli_main

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join("tests", "fixtures", "analysis")


def lint_fixture(name):
    findings, errors = lint_paths([os.path.join(FIXTURES, name)], ROOT)
    assert not errors, errors
    return findings


# -- per-rule positive + negative cases -------------------------------

RULE_CASES = {
    "trace-nondeterminism": 3,   # clock, np.random, random via lax.scan
    "unordered-iteration": 3,    # set for-loop, set comprehension, listdir
    "traced-branch": 3,          # if, while, derived value
    "host-sync": 6,              # traced float + 5 hot-path syncs
    #                              (incl. one nested in a self-assign)
    "use-after-donation": 2,     # read-after, loop-no-rebind
    "divergent-collective": 4,   # process_index, filesystem, except,
    #                              control-dependent flag
    "unregistered-jit": 2,       # decorator-form + call-form
    "unregistered-kernel": 2,    # unpinned site + unknown program name
    "obs-in-hot-path": 2,        # .span() + .event() on a marked hot path
}


@pytest.mark.parametrize("rule", sorted(RULE_CASES))
def test_rule_fires_on_seeded_violations(rule):
    fname = f"bad_{rule.replace('-', '_')}.py"
    findings = lint_fixture(fname)
    hits = [f for f in findings if f.rule == rule]
    assert len(hits) == RULE_CASES[rule], [f.render() for f in findings]
    # the bad fixture must not trip OTHER rules (each file seeds exactly
    # its own hazard class)
    assert len(findings) == len(hits), [f.render() for f in findings]


@pytest.mark.parametrize("rule", sorted(RULE_CASES))
def test_rule_silent_on_corrected_twin(rule):
    fname = f"good_{rule.replace('-', '_')}.py"
    findings = lint_fixture(fname)
    assert findings == [], [f.render() for f in findings]


def test_every_shipped_rule_has_fixture_pair():
    shipped = set(RULES_BY_NAME)
    assert shipped == set(RULE_CASES), (
        "a rule shipped without fixture coverage (or a fixture outlived "
        "its rule) — every rule needs a bad_/good_ pair and a RULE_CASES "
        "entry")
    for rule in shipped:
        stem = rule.replace("-", "_")
        for prefix in ("bad_", "good_"):
            assert os.path.exists(os.path.join(
                ROOT, FIXTURES, f"{prefix}{stem}.py"))


# -- suppression machinery --------------------------------------------


def _lint_source(tmp_path, source, name="mod.py"):
    p = tmp_path / name
    p.write_text(source)
    return lint_paths([str(p)], ROOT)[0]


BRANCHY = """\
import jax

@jax.jit
def f(x):
    {comment_above}if x > 0:{comment_inline}
        return x
    return -x
"""


def test_suppression_same_line(tmp_path):
    findings = _lint_source(tmp_path, BRANCHY.format(
        comment_above="",
        comment_inline="  # tpudp: lint-ok(traced-branch): test"))
    assert findings == []


def test_suppression_comment_block_above(tmp_path):
    findings = _lint_source(tmp_path, BRANCHY.format(
        comment_above="# tpudp: lint-ok(traced-branch): spans a\n"
                      "    # multi-line justification block\n    ",
        comment_inline=""))
    assert findings == []


def test_suppression_wrong_rule_does_not_mask(tmp_path):
    findings = _lint_source(tmp_path, BRANCHY.format(
        comment_above="",
        comment_inline="  # tpudp: lint-ok(host-sync): wrong rule"))
    rules = {f.rule for f in findings}
    assert "traced-branch" in rules          # still reported
    assert "useless-suppression" in rules    # and the stale excuse too


def test_useless_suppression_reported(tmp_path):
    findings = _lint_source(
        tmp_path,
        "x = 1  # tpudp: lint-ok(traced-branch): nothing here\n")
    assert [f.rule for f in findings] == ["useless-suppression"]


def test_docstring_mention_is_not_a_suppression(tmp_path):
    findings = _lint_source(
        tmp_path,
        '"""Docs may mention # tpudp: lint-ok(traced-branch) freely."""\n'
        "x = 1\n")
    assert findings == []


# -- CLI contract ------------------------------------------------------


def test_lint_cli_exit_codes(capsys):
    bad = os.path.join(FIXTURES, "bad_traced_branch.py")
    good = os.path.join(FIXTURES, "good_traced_branch.py")
    assert cli_main(["lint", bad]) == 1
    assert cli_main(["lint", good]) == 0
    out = capsys.readouterr().out
    assert "traced-branch" in out


@pytest.mark.slow  # real subprocess pays the full jax import (~7s)
def test_lint_cli_nonzero_composes_with_pipefail():
    """`python -m tpudp.analysis` must exit nonzero on findings so
    `set -o pipefail` harnesses catch it (ISSUE 8 satellite);
    test_lint_cli_exit_codes pins the same contract in-process on the
    fast tier."""
    proc = subprocess.run(
        ["bash", "-c",
         "set -o pipefail; "
         f"{sys.executable} -m tpudp.analysis lint "
         f"{os.path.join(FIXTURES, 'bad_traced_branch.py')} | cat"],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1, proc.stdout + proc.stderr


def test_lint_cli_missing_path_is_an_error(capsys):
    """A typo'd path must not turn the gate green by linting nothing."""
    assert cli_main(["lint", "tpudp/no_such_dir"]) == 2
    assert "no such path" in capsys.readouterr().err


def test_audit_cli_corrupt_lock_is_diagnosed(tmp_path, capsys):
    """A merge-conflicted lockfile gets the exit-1 diagnostic, not a
    JSONDecodeError traceback — and fails fast, before any tracing."""
    bad = tmp_path / "lock.json"
    bad.write_text("<<<<<<< conflict marker\n")
    assert cli_main(["audit", "--lock", str(bad)]) == 1
    assert "unreadable lockfile" in capsys.readouterr().err


def test_list_rules_catalogue(capsys):
    assert cli_main(["lint", "--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in RULES_BY_NAME:
        assert rule in out


# -- auditor -----------------------------------------------------------


@pytest.fixture()
def capture(audit_capture):
    return audit_capture  # session-scoped (conftest) — captured once


def test_audit_mutated_program_fails_by_name(capture):
    """Adding a host callback to a step program's trace must fail the
    audit naming that program (the ISSUE 8 acceptance example).  Only
    the mutated program is re-traced — a lock/capture SUBSET keeps the
    test at one trace instead of eleven."""
    import jax

    from tpudp.analysis import audit
    from tpudp.analysis.programs import build_programs

    name = "serve.decode_step@s2m32"
    fn, args = build_programs()[name]

    def hacked(*a):
        out = fn(*a)
        jax.debug.callback(lambda: None)  # the seeded host round trip
        return out

    sub_lock = dict(capture,
                    programs={name: capture["programs"][name]})
    problems = audit.compare(sub_lock,
                             audit.capture({name: (hacked, args)}))
    assert len(problems) == 1
    assert name in problems[0]
    assert "callbacks 0 -> 1" in problems[0]


def test_audit_update_then_check_roundtrip(capture, tmp_path):
    from tpudp.analysis import audit

    lock_path = tmp_path / "lock.json"
    audit.write_lock(str(lock_path), capture)
    assert audit.compare(audit.load_lock(str(lock_path)), capture) == []


def test_audit_missing_program_named(capture):
    from tpudp.analysis import audit

    pruned = json.loads(json.dumps(capture))
    removed = "train.step_dp_ring@mesh8"
    del pruned["programs"][removed]
    # lock knows it, live tree lost it
    problems = audit.compare(capture, pruned)
    assert any(removed in p and "no longer registered" in p
               for p in problems)
    # live tree grew one the lock doesn't know
    problems = audit.compare(pruned, capture)
    assert any(removed in p and "not in the lockfile" in p
               for p in problems)


def test_audit_collective_sequence_change_named(capture):
    from tpudp.analysis import audit

    mutated = json.loads(json.dumps(capture))
    name = "train.step_dp_ring@mesh8"
    mutated["programs"][name]["collectives"] = ["psum"]
    problems = audit.compare(capture, mutated)
    assert any(name in p and "collective sequence changed" in p
               for p in problems)


def test_audit_stale_sources_reported(capture):
    from tpudp.analysis import audit

    stale = json.loads(json.dumps(capture))
    stale["sources"]["tpudp/serve/engine.py"] = "deadbeef"
    problems = audit.compare(capture, stale)
    assert any("stale source digests" in p and "engine.py" in p
               for p in problems)
    # symmetric: a source REMOVED from AUDIT_SOURCES (file renamed/
    # dropped) without --update leaves a rotted lock entry the tier-1
    # gate must reject too, matching sources_stale()'s jax-free verdict
    shrunk = json.loads(json.dumps(capture))
    del shrunk["sources"]["tpudp/parallel/ring.py"]
    problems = audit.compare(capture, shrunk)
    assert any("stale source digests" in p and "ring.py" in p
               for p in problems)


def test_audit_registry_covers_trace_counters():
    """Every TRACE_COUNTS key the serve layer can bump has a registered
    audit program — a jit added with a counter but no registry entry
    would satisfy the linter yet dodge the trace lock.  The key set is
    DERIVED from the actual bump sites by AST, so it cannot go stale."""
    import ast
    import glob

    from tpudp.analysis.programs import (TRACE_COUNTER_PROGRAMS,
                                         build_programs)

    bumped = set()
    for path in glob.glob(os.path.join(ROOT, "tpudp", "serve", "*.py")):
        for node in ast.walk(ast.parse(open(path).read())):
            if (isinstance(node, ast.AugAssign)
                    and isinstance(node.target, ast.Subscript)
                    and isinstance(node.target.value, ast.Name)
                    and node.target.value.id == "TRACE_COUNTS"
                    and isinstance(node.target.slice, ast.Constant)):
                bumped.add(node.target.slice.value)
    assert bumped, "AST scan found no TRACE_COUNTS bump sites at all?"
    assert bumped == set(TRACE_COUNTER_PROGRAMS), (
        "TRACE_COUNTS keys and the audit registry map diverged — add "
        "the new program to programs.build_programs() AND "
        "TRACE_COUNTER_PROGRAMS (then `audit --update`)")
    names = {n.split("@")[0] for n in build_programs()}
    missing = set(TRACE_COUNTER_PROGRAMS.values()) - names
    assert not missing, (
        f"mapped programs with no registry builder: {sorted(missing)}")


def test_sources_stale_is_jax_free_and_detects(tmp_path):
    """sources_stale is the stale-lock check that needs no jax: prove
    it works in a jax-less subprocess (imports of the lint half must
    not drag jax in)."""
    code = (
        "import importlib.util, json, sys, os\n"
        f"pkg = {os.path.join(ROOT, 'tpudp', 'analysis')!r}\n"
        "spec = importlib.util.spec_from_file_location(\n"
        "    '_a', os.path.join(pkg, '__init__.py'),\n"
        "    submodule_search_locations=[pkg])\n"
        "mod = importlib.util.module_from_spec(spec)\n"
        "sys.modules['_a'] = mod\n"
        "spec.loader.exec_module(mod)\n"
        "from _a import audit\n"
        f"stale = audit.sources_stale(os.path.join({ROOT!r}, 'tools',\n"
        "    'trace_lock.json'))\n"
        "assert 'jax' not in sys.modules, 'lint half imported jax!'\n"
        "print(json.dumps(stale))\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    json.loads(proc.stdout)  # parseable list
