"""tpudp.analysis.protocol + budget — the cross-host protocol verifier,
the vote-machine model checker, and the per-program resource ledger.

The rule contract mirrors test_analysis.py: every protocol rule must
FIRE on its seeded violation fixture with a pinned count and stay
SILENT on the corrected twin.  The mutation tests are the ISSUE 12
acceptance bar: re-introducing PR 7's reviewed entry-probe bug (a
per-host listing deciding entry into the collective restore) and a
swapped vote/recover order into copies of resilience.py must each fail
the verifier naming the rule and the mutated line; dropping the
completion-vote park from the protocol spec must be caught by the
interleaving explorer; and a +1-collective or doubled-live-buffer
mutation in a pinned program must fail the audit naming the program
and the metric.
"""

import json
import os
import subprocess
import sys

import pytest

from tpudp.analysis import PROTOCOL_RULE_NAMES, lint_paths
from tpudp.analysis.cli import main as cli_main
from tpudp.analysis.protocol import (PROTOCOL_MODULES, VoteSpec,
                                     explore_vote_machine,
                                     extract_vote_spec, verify_paths)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join("tests", "fixtures", "analysis")
MARKER = "# tpudp: protocol-module\n"


def verify_fixture(name):
    findings, errors = verify_paths([os.path.join(FIXTURES, name)], ROOT)
    assert not errors, errors
    return findings


# -- per-rule positive + negative fixture cases ------------------------

PROTOCOL_RULE_CASES = {
    "protocol-divergent-entry": 2,   # direct probe + interprocedural
    "protocol-order-divergence": 1,  # swapped vote/barrier across arms
    "protocol-early-exit": 2,        # early return + early raise
    "protocol-divergent-loop": 2,    # for-over-listdir + tainted while
}


@pytest.mark.parametrize("rule", sorted(PROTOCOL_RULE_CASES))
def test_protocol_rule_fires_on_seeded_violations(rule):
    fname = f"bad_{rule.replace('-', '_')}.py"
    findings = verify_fixture(fname)
    hits = [f for f in findings if f.rule == rule]
    assert len(hits) == PROTOCOL_RULE_CASES[rule], \
        [f.render() for f in findings]
    assert len(findings) == len(hits), [f.render() for f in findings]


@pytest.mark.parametrize("rule", sorted(PROTOCOL_RULE_CASES))
def test_protocol_rule_silent_on_corrected_twin(rule):
    fname = f"good_{rule.replace('-', '_')}.py"
    findings = verify_fixture(fname)
    assert findings == [], [f.render() for f in findings]


def test_every_protocol_rule_has_fixture_pair():
    assert set(PROTOCOL_RULE_CASES) == set(PROTOCOL_RULE_NAMES), (
        "a protocol rule shipped without fixture coverage (or a fixture "
        "outlived its rule) — every rule needs a bad_/good_ pair, a "
        "PROTOCOL_RULE_CASES entry, and a PROTOCOL_RULE_NAMES entry")
    for rule in PROTOCOL_RULE_CASES:
        stem = rule.replace("-", "_")
        for prefix in ("bad_", "good_"):
            assert os.path.exists(os.path.join(
                ROOT, FIXTURES, f"{prefix}{stem}.py"))


# -- suppression machinery across the two passes -----------------------


def _paths(tmp_path, source, name="mod.py"):
    p = tmp_path / name
    p.write_text(source)
    return [str(p)]


PROBE = (MARKER
         + "import os\n\n"
           "def resume(root):\n"
           "    {suppress}if os.path.exists(root):\n"
           "        gather_host_values(1)  # noqa: F821\n")


def test_protocol_suppression_masks_finding(tmp_path):
    src = PROBE.format(
        suppress="# tpudp: lint-ok(protocol-divergent-entry): test\n    ")
    findings, _ = verify_paths(_paths(tmp_path, src), ROOT)
    # suppression anchored above the collective site's line
    src2 = PROBE.format(suppress="")
    src2 = src2.replace(
        "        gather_host_values(1)  # noqa: F821",
        "        # tpudp: lint-ok(protocol-divergent-entry): test\n"
        "        gather_host_values(1)  # noqa: F821")
    findings2, _ = verify_paths(_paths(tmp_path, src2, "mod2.py"), ROOT)
    assert findings2 == [], [f.render() for f in findings2]
    # the unanchored one (above the IF, not the site) must NOT mask
    assert sorted(f.rule for f in findings) == [
        "protocol-divergent-entry", "useless-suppression"]


def test_lint_defers_protocol_rule_names(tmp_path):
    """In a protocol-scoped file, a protocol-rule suppression is not
    `useless` to the LINT pass — the protocol pass owns those names
    (the ISSUE 12 small fix); a name belonging to NEITHER pass is
    still flagged by lint.  (Out of protocol scope lint flags both —
    test_out_of_scope_stale_protocol_suppression_caught_by_lint.)"""
    src = (MARKER
           + "x = 1  # tpudp: lint-ok(protocol-divergent-entry): lint "
             "must defer this name\n"
             "y = 2  # tpudp: lint-ok(no-such-rule): typo still caught\n")
    findings, _ = lint_paths(_paths(tmp_path, src), ROOT)
    assert [(f.rule, f.line) for f in findings] == [
        ("useless-suppression", 3)]


def test_protocol_pass_flags_stale_protocol_suppressions(tmp_path):
    """A suppression naming a protocol rule that matches nothing is a
    finding of the PROTOCOL pass — stale exemptions cannot linger after
    a refactor."""
    src = (MARKER
           + "def f():\n"
             "    return 1  # tpudp: lint-ok(protocol-early-exit): stale\n")
    findings, _ = verify_paths(_paths(tmp_path, src), ROOT)
    assert [f.rule for f in findings] == ["useless-suppression"]
    assert "protocol-early-exit" in findings[0].message


def test_identical_label_sequences_compare_equal(tmp_path):
    """Two arms issuing the SAME collective sequence at different call
    sites rendezvous identically — no finding (review regression: site
    indices are per-node and must not be compared raw)."""
    src = (MARKER
           + "import os\n\n\n"
             "def f(root):\n"
             "    if os.path.exists(root):\n"
             "        gather_host_values(1)  # noqa: F821\n"
             "    else:\n"
             "        gather_host_values(2)  # noqa: F821\n")
    findings, _ = verify_paths(_paths(tmp_path, src), ROOT)
    assert findings == [], [f.render() for f in findings]


def test_for_loop_target_carries_taint(tmp_path):
    """A host-local fact bound through a for target (the per-host
    listing item) must taint downstream guards (review regression: the
    exact PR 7 class, spelled through iteration)."""
    src = (MARKER
           + "import os\n\n\n"
             "def f(root):\n"
             "    d = None\n"
             "    for name in sorted(os.listdir(root)):\n"
             "        d = name\n"
             "    if d:\n"
             "        gather_host_values(1)  # noqa: F821\n")
    findings, _ = verify_paths(_paths(tmp_path, src), ROOT)
    assert [f.rule for f in findings] == ["protocol-divergent-entry"], \
        [f.render() for f in findings]


def test_out_of_scope_stale_protocol_suppression_caught_by_lint(tmp_path):
    """A lint-ok(protocol-*) in a file the protocol verifier never
    reads must be flagged by LINT — otherwise a module renamed out of
    PROTOCOL_MODULES keeps its stale exemptions forever (review
    regression on the ISSUE 12 'small fix')."""
    src = "x = 1  # tpudp: lint-ok(protocol-early-exit): stale\n"
    findings, _ = lint_paths(_paths(tmp_path, src), ROOT)
    assert [f.rule for f in findings] == ["useless-suppression"]


def test_truncated_function_is_reported(tmp_path):
    """A function exceeding the sequence bound must surface as an
    ERROR (gate-failing), never verify silently-partial (review
    regression: cfg.py's documented truncation contract)."""
    from tpudp.analysis.cfg import MAX_SEQ

    body = "".join(f"    gather_host_values({i})  # noqa: F821\n"
                   for i in range(MAX_SEQ + 4))
    src = MARKER + "def f(root):\n" + body
    findings, errors = verify_paths(_paths(tmp_path, src), ROOT)
    assert errors and "incomplete" in errors[0], (findings, errors)


def test_sibling_ternaries_all_fork(tmp_path):
    """EVERY collective-bearing ternary in one expression forks — the
    second sibling's per-host rendezvous-entry decision must not be
    linear-scanned away (review regression)."""
    src = (MARKER
           + "import os\n\n\n"
             "def f(root, uniform_flag):\n"
             "    local = os.path.exists(root)\n"
             "    return (gather_host_values(1) if uniform_flag"
             " else 0,\n"
             "            all_hosts_ok(True, 0) if local else 1)"
             "  # noqa: F821\n")
    findings, _ = verify_paths(_paths(tmp_path, src), ROOT)
    # the fork sits in a `return` expression, so the arm missing the
    # rendezvous classifies as an early exit — same divergence family,
    # what matters is that the SECOND ternary is seen at all
    assert [f.rule for f in findings] == ["protocol-early-exit"], \
        [f.render() for f in findings]
    assert "all_hosts_ok" in findings[0].message


def test_finally_collectives_cover_exit_paths(tmp_path):
    """A rendezvous in a `finally` runs on return/raise paths too —
    barrier-in-finally cleanup must NOT read as an early exit skipping
    the collective (review regression)."""
    src = (MARKER
           + "import os\n\n\n"
             "def f(root):\n"
             "    try:\n"
             "        if not os.path.exists(root):\n"
             "            raise RuntimeError('gone')\n"
             "        x = 1\n"
             "    finally:\n"
             "        gather_host_values(1)  # noqa: F821\n")
    findings, _ = verify_paths(_paths(tmp_path, src), ROOT)
    assert findings == [], [f.render() for f in findings]


def test_marker_with_trailing_text_agrees_across_passes(tmp_path):
    """A `# tpudp: protocol-module` marker with trailing text must put
    the file in BOTH passes' scope (review regression: the two passes
    parsed markers differently, re-opening the neither-pass-flags-it
    gap for stale suppressions)."""
    src = ("# tpudp: protocol-module (test fixture)\n"
           "import os\n\n\n"
           "def f(root):\n"
           "    if os.path.exists(root):\n"
           "        gather_host_values(1)  # noqa: F821\n"
           "    x = 1  # tpudp: lint-ok(protocol-early-exit): stale\n")
    paths = _paths(tmp_path, src)
    pfind, _ = verify_paths(paths, ROOT)
    assert sorted(f.rule for f in pfind) == [
        "protocol-divergent-entry", "useless-suppression"], \
        [f.render() for f in pfind]  # verified AND stale-flagged here
    lfind, _ = lint_paths(paths, ROOT)
    assert all(f.rule != "useless-suppression" or
               "protocol" not in f.message for f in lfind)


def test_within_tolerance_budget_delta_names_the_lock_not_the_math():
    """A record differing ONLY by a within-tolerance budget (e.g. a
    donation-table edit, identical jaxpr) must say the LOCK is stale —
    never 'the traced math itself differs' (review regression)."""
    from tpudp.analysis import audit

    base = {"version": audit.LOCK_VERSION, "jax": "x",
            "geometry": {"platform": "cpu", "devices": 8}}
    rec = {"fingerprint": "abc", "eqns": 1, "collectives": [],
           "callbacks": 0, "transfers": 0,
           "budget": {"peak_live_bytes": 1000, "arg_bytes": 1,
                      "out_bytes": 1, "collective_payload_bytes": 0}}
    rec2 = json.loads(json.dumps(rec))
    rec2["budget"]["peak_live_bytes"] = 1050  # +5%, inside the band
    problems = audit.compare(dict(base, programs={"p@x": rec}),
                             dict(base, programs={"p@x": rec2}))
    assert len(problems) == 1, problems
    assert "regenerate with --update" in problems[0]
    assert "traced math itself differs" not in problems[0]


def test_lock_has_ledgers_is_the_shared_definition():
    """`budget --table` and the tier-1 presence test must agree on
    budget-completeness — one helper, not inline rules (review
    regression)."""
    from tpudp.analysis.budget import lock_has_ledgers

    good = {"geometry": {"platform": "cpu", "devices": 8},
            "programs": {"p": {"budget": {}}}}
    assert lock_has_ledgers(good)
    assert not lock_has_ledgers({**good, "geometry": None})
    assert not lock_has_ledgers(
        {**good, "programs": {"p": {}}})
    assert not lock_has_ledgers({**good, "programs": {}})
    # the consumer actually calls it
    import inspect

    from tpudp.analysis import cli as _cli
    assert "lock_has_ledgers" in inspect.getsource(_cli._cmd_budget)


def test_match_statement_arms_are_visible(tmp_path):
    """Collectives under `match` case arms must be enumerated like If
    arms — a host-local subject with a rendezvous in one case is the
    same divergence class (review regression: ast.Match was invisible
    to the path enumerator)."""
    src = (MARKER
           + "import os\n\n\n"
             "def f(root):\n"
             "    match os.path.exists(root):\n"
             "        case True:\n"
             "            gather_host_values(1)  # noqa: F821\n"
             "        case _:\n"
             "            pass\n")
    findings, _ = verify_paths(_paths(tmp_path, src), ROOT)
    assert [f.rule for f in findings] == ["protocol-divergent-entry"], \
        [f.render() for f in findings]


def test_program_donations_mirror_rules_tables():
    """PROGRAM_DONATIONS (the budget pass's donation facts) must equal
    the linter's DONATING tables (the PR 8 mirror of the runtime
    donate_argnums) — a donate change updated in one table but not the
    other would silently re-baseline peak_live_bytes wrong (review
    regression: no drift check between the two mirrors)."""
    from tpudp.analysis.programs import PROGRAM_DONATIONS
    from tpudp.analysis.rules import DONATING

    mirror = {
        "serve.decode_step": "decode_step",
        "serve.verify_step": "verify_step",
        "serve.prefill_chunk": "prefill_step",
        "serve.fused_decode": "fused_step",
        "serve.fused_decode_stream": "fused_step",
        "serve.decode_paged": "decode_paged",
        # the Pallas kernel twin dispatches through the same
        # _ModelState.decode_paged attribute (same signature/donations)
        "serve.decode_paged_kernel": "decode_paged",
        # the latent-attention expert family's two programs (PR 34)
        # dispatch through the same two attributes, same donations
        "serve.decode_paged_latent": "decode_paged",
        "serve.prefill_paged_latent": "prefill_paged",
        # ... and their twins through the latent_attn Mosaic call (PR 37)
        "serve.decode_paged_latent_kernel": "decode_paged",
        "serve.prefill_paged_latent_kernel": "prefill_paged",
        # the window-and-full-attention expert family's two (PR 36)
        "serve.decode_paged_windowed": "decode_paged",
        "serve.prefill_paged_windowed": "prefill_paged",
        "serve.verify_paged": "verify_paged",
        # ... and likewise for the remaining ISSUE 17 kernel twins:
        # each dispatches through the same engine attribute as its
        # einsum sibling, so signatures and donations are shared.
        "serve.verify_paged_kernel": "verify_paged",
        "serve.prefill_paged": "prefill_paged",
        "serve.prefill_paged_kernel": "prefill_paged",
        "serve.fused_decode_paged": "fused_paged",
        "serve.fused_decode_paged_stream": "fused_paged",
        "serve.fused_decode_paged_kernel": "fused_paged",
        # On-device speculation: fused window + tree-verify programs
        # (dense and paged twins) donate the target arena/pool + obs
        # counters; the draft KV is loop-carry scratch with no row.
        "serve.fused_spec_decode": "fused_spec_step",
        "serve.fused_spec_decode_stream": "fused_spec_step",
        "serve.fused_spec_paged": "fused_spec_paged",
        "serve.fused_spec_paged_stream": "fused_spec_paged",
        "serve.fused_spec_paged_kernel": "fused_spec_paged",
        "serve.tree_verify": "tree_step",
        "serve.tree_verify_paged": "tree_paged",
        "serve.tree_verify_paged_kernel": "tree_paged",
        "prefix.copy_block_in": "copy_block_in",
        "prefix.copy_block_out": "copy_block_out",
        "train.step_single": "train_step",
        "train.step_dp_allreduce": "train_step",
        "train.step_dp_ring": "train_step",
        # 1F1B MPMD pipeline programs (ISSUE 19): the Trainer drives the
        # step through the same strategy seam as every other train_step,
        # donating the TrainState at arg 0 (pp_eval reads params only
        # and is donation-free).
        "train.pp_1f1b": "train_step",
        "train.pp_1f1b_int": "train_step",
        # SDC-fingerprint twins (ISSUE 20): the SAME train step with
        # the TrainState's sdc_fp slot allocated — the checksum reads
        # post-update VALUES, so the donation facts are unchanged.
        "train.step_single_sdc": "train_step",
        "train.step_dp_allreduce_sdc": "train_step",
    }
    for prog, callee in mirror.items():
        # The serve programs take the weights FIRST (the fused
        # speculative ones the draft weights second): the engine binds
        # them (_ModelState), so DONATING records call-site positions
        # and PROGRAM_DONATIONS the raw program's, one (two) further on.
        lead = (0 if not prog.startswith("serve.")
                else 2 if "fused_spec" in prog else 1)
        assert PROGRAM_DONATIONS[prog] == tuple(
            i + lead for i in DONATING[callee]), (
            f"{prog} donation facts drifted from rules.DONATING"
            f"[{callee!r}] — update both mirrors together")
    # every registry program is either mirrored above or explicitly
    # donation-free
    free = {p for p, d in PROGRAM_DONATIONS.items() if d == ()}
    assert set(PROGRAM_DONATIONS) == set(mirror) | free


def test_old_lock_version_fails_with_version_diagnostic(capture):
    """A pre-budget lockfile (version 1, no geometry/budget) must fail
    with the version diagnostic and its --update advice — never a
    confusing geometry/field mismatch (review regression: schema grew
    without a LOCK_VERSION bump)."""
    from tpudp.analysis import audit

    assert capture["version"] == audit.LOCK_VERSION == 2
    old = json.loads(json.dumps(capture))
    old["version"] = 1
    del old["geometry"]
    for rec in old["programs"].values():
        del rec["budget"]
    problems = audit.compare(old, capture)
    assert len(problems) == 1 and "lock version" in problems[0], problems


def test_budget_subcommand_gates_on_identity_skew():
    """`budget` must share audit's jax/geometry precheck so a skewed
    lock yields ONE named diagnostic, not a per-program budget storm
    (review regression)."""
    from tpudp.analysis import audit

    lock = {"jax": "0.0.1-other", "geometry": {"platform": "cpu",
                                               "devices": 8}}
    current = {"jax": "9.9.9", "geometry": {"platform": "cpu",
                                            "devices": 8}}
    skew = audit.identity_skew(lock, current)
    assert len(skew) == 1 and "jax version skew" in skew[0]
    current = dict(current, jax="0.0.1-other",
                   geometry={"platform": "tpu", "devices": 4})
    skew = audit.identity_skew(lock, current)
    assert len(skew) == 1 and "geometry skew" in skew[0]
    # and the cli path actually consults it (source-level pin: the
    # budget command must call identity_skew before compare_budgets)
    import inspect

    from tpudp.analysis import cli as _cli
    src = inspect.getsource(_cli._cmd_budget)
    assert "identity_skew" in src


# -- tree gate ----------------------------------------------------------


def test_protocol_modules_all_exist():
    for rel in PROTOCOL_MODULES:
        assert os.path.exists(os.path.join(ROOT, rel)), (
            f"PROTOCOL_MODULES names {rel} which does not exist — scope "
            f"rotted after a refactor")


# -- mutation tests (the acceptance bar) --------------------------------


def _mutated_copy(tmp_path, old, new, name):
    src = open(os.path.join(ROOT, "tpudp", "resilience.py")).read()
    assert old in src, "mutation target drifted — update the test"
    mutated = MARKER + src.replace(old, new)
    p = tmp_path / name
    p.write_text(mutated)
    return str(p), mutated


def test_mutation_entry_probe_bug_is_named(tmp_path):
    """PR 7's reviewed bug, re-introduced: a per-host listing probe
    deciding entry into the collective restore.  The verifier must name
    the rule and the mutated line."""
    path, mutated = _mutated_copy(
        tmp_path,
        "if coordinated_any(latest_step_dir(checkpoint_dir) is not None):",
        "if latest_step_dir(checkpoint_dir) is not None:",
        "resilience_probe.py")
    findings, errors = verify_paths(
        [path, os.path.join("tpudp", "utils", "checkpoint.py")], ROOT)
    assert not errors, errors
    want_line = next(i + 1 for i, line in enumerate(mutated.splitlines())
                     if line.strip()
                     == "if latest_step_dir(checkpoint_dir) is not None:")
    assert [(f.rule, f.line) for f in findings] == [
        ("protocol-early-exit", want_line)], \
        [f.render() for f in findings]
    assert "latest_step_dir" in findings[0].message
    assert "os.listdir" in findings[0].message  # the reason CHAIN


def test_mutation_swapped_vote_order_is_named(tmp_path):
    """Swapping the vote/recover order in ONE fault arm diverges the
    rendezvous order across the exception arms; the verifier names the
    swapped site EXACTLY — the reviewed single-host suppressions in the
    copy absorb their own divergences without masking this one."""
    old = ("cur_start, cur_skip = self._coordinated_recover(\n"
           "                            self._vote(code), e)")
    new = ("worst = self._coordinated_recover(code, e)\n"
           "                        cur_start, cur_skip = "
           "self._vote(worst), 0")
    path, mutated = _mutated_copy(tmp_path, old, new,
                                  "resilience_swap.py")
    findings, errors = verify_paths(
        [path, os.path.join("tpudp", "utils", "checkpoint.py")], ROOT)
    assert not errors, errors
    want_line = next(
        i + 1 for i, line in enumerate(mutated.splitlines())
        if line.strip() == "worst = self._coordinated_recover(code, e)")
    assert [(f.rule, f.line) for f in findings] == [
        ("protocol-order-divergence", want_line)], \
        [f.render() for f in findings]
    assert "_coordinated_recover" in findings[0].message
    assert "_vote" in findings[0].message


def test_unmutated_copy_is_clean(tmp_path):
    """Control: the marker-prefixed copy of the REAL resilience.py must
    verify clean — the mutation tests' findings are caused by the
    mutations alone."""
    path, _ = _mutated_copy(tmp_path, "coordinated_any(",
                            "coordinated_any(", "resilience_ctl.py")
    findings, errors = verify_paths(
        [path, os.path.join("tpudp", "utils", "checkpoint.py")], ROOT)
    assert not errors, errors
    assert findings == [], [f.render() for f in findings]


# -- vote-machine model checker -----------------------------------------


def test_vote_machine_deadlock_free_within_bounds():
    """The spec extracted from the LIVE resilience source must explore
    clean: completion park + bounded timeout present, no deadlock, no
    healthy-pod timeout, across 2 and 3 hosts."""
    src = open(os.path.join(ROOT, "tpudp", "resilience.py")).read()
    for hosts in (2, 3):
        spec = extract_vote_spec(src, n_hosts=hosts, max_faults=2,
                                 max_crashes=1)
        assert spec.completion_park and spec.bounded_timeout
        result = explore_vote_machine(spec)
        assert result["violations"] == [], result["violations"][:3]
        assert result["states"] > 50  # the exploration actually ran


def test_vote_machine_catches_dropped_completion_park():
    """The deliberately broken spec (ISSUE 12 acceptance): deleting the
    clean finisher's completion-vote park strands a late faulter — the
    explorer reports a healthy pod losing a host to the vote timeout,
    end to end from the mutated source."""
    src = open(os.path.join(ROOT, "tpudp", "resilience.py")).read()
    target = "worst = self._vote(OUTCOME_OK)"
    assert target in src, "completion-vote spelling drifted — update test"
    spec = extract_vote_spec(src.replace(target, "worst = OUTCOME_OK"))
    assert spec.completion_park is False  # extraction saw the drop
    result = explore_vote_machine(spec)
    kinds = {v["kind"] for v in result["violations"]}
    assert "spurious-timeout" in kinds, result
    # and with the timeout ALSO gone, the same drop is a hard deadlock
    frozen = VoteSpec(completion_park=False, bounded_timeout=False)
    kinds = {v["kind"]
             for v in explore_vote_machine(frozen)["violations"]}
    assert "deadlock" in kinds


def test_vote_machine_crash_paths_resolve_via_timeout():
    """A real crash is survivable ONLY through the bounded timeout:
    with it, no deadlock (survivors hard-exit for relaunch); without
    it, the crash deadlocks the vote — the model agrees with why
    vote_timeout_s exists."""
    ok = explore_vote_machine(VoteSpec(n_hosts=2, max_crashes=1))
    assert all(v["kind"] != "deadlock" for v in ok["violations"])
    assert ok["violations"] == []  # timeouts after a crash are not
    # spurious — only healthy-pod timeouts are violations
    bad = explore_vote_machine(VoteSpec(n_hosts=2, max_crashes=1,
                                        bounded_timeout=False))
    assert any(v["kind"] == "deadlock" for v in bad["violations"])


# -- budget ledger ------------------------------------------------------


@pytest.fixture()
def capture(audit_capture):
    return audit_capture


def test_budget_ledger_in_every_program(capture):
    for name, rec in capture["programs"].items():
        b = rec.get("budget")
        assert b, f"{name} captured without a budget ledger"
        assert b["peak_live_bytes"] >= b["out_bytes"] > 0, (name, b)
        assert b["arg_bytes"] > 0, (name, b)
    # geometry identity rides in the capture
    assert capture["geometry"] == {"platform": "cpu", "devices": 8}
    # comms canaries: the DP programs move collective bytes, the serve
    # programs (single-chip arena) move none
    progs = capture["programs"]
    assert progs["train.step_dp_allreduce@mesh8"]["budget"][
        "collective_payload_bytes"] > 0
    assert progs["train.step_dp_ring@mesh8"]["budget"][
        "collective_payload_bytes"] > progs[
        "train.step_dp_allreduce@mesh8"]["budget"][
        "collective_payload_bytes"], \
        "the ring schedule moves more bytes than tree-allreduce"
    assert progs["serve.decode_step@s2m32"]["budget"][
        "collective_payload_bytes"] == 0


def test_budget_doubled_live_buffer_fails_audit_by_name(capture):
    """ISSUE 12 acceptance: a doubled live buffer in a pinned program
    fails the audit with the program AND metric named."""
    import jax
    import jax.numpy as jnp

    from tpudp.analysis import audit
    from tpudp.analysis.programs import PROGRAM_DONATIONS, build_programs

    name = "serve.decode_step@s2m32"
    fn, args = build_programs()[name]

    def fat(*a):  # a full second cache copy held live across the step
        pad = jax.tree.map(lambda x: x + 0, a[0])
        outs = fn(*a)
        return outs, jax.tree.map(lambda x: jnp.float32(x.sum()), pad)

    hacked = audit.fingerprint(
        fat, args, PROGRAM_DONATIONS["serve.decode_step"])
    base = capture["programs"][name]
    grown = (hacked["budget"]["peak_live_bytes"]
             / base["budget"]["peak_live_bytes"])
    assert grown > 1.10, "mutation did not breach the tolerance band"
    sub_lock = dict(capture, programs={name: base})
    problems = audit.compare(
        sub_lock, dict(capture, programs={name: hacked}))
    budget_problems = [p for p in problems
                       if name in p and "peak_live_bytes" in p]
    assert budget_problems, problems


def test_budget_extra_collective_fails_audit_by_name(capture):
    """ISSUE 12 acceptance: a +1 collective in a pinned program fails
    the audit naming the program and the comms metric (alongside the
    PR 8 collective-sequence delta)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from tpudp.analysis import audit
    from tpudp.analysis.programs import build_programs
    from tpudp.mesh import make_mesh

    name = "train.step_dp_allreduce@mesh8"
    fn, args = build_programs()[name]
    mesh = make_mesh(8)

    def extra(*a):
        out = fn(*a)
        bonus = jax.shard_map(
            lambda x: jax.lax.psum(x, "data"), mesh=mesh,
            in_specs=P("data"), out_specs=P())(
                jnp.zeros((8,), jnp.float32))
        return out, bonus

    hacked = audit.fingerprint(extra, args, (0,))
    base = capture["programs"][name]
    assert len(hacked["collectives"]) == len(base["collectives"]) + 1
    problems = audit.compare(
        dict(capture, programs={name: base}),
        dict(capture, programs={name: hacked}))
    assert any(name in p and "collective_payload_bytes" in p
               for p in problems), problems
    assert any(name in p and "collective sequence changed" in p
               for p in problems), problems


def test_budget_tolerance_band():
    from tpudp.analysis.budget import compare_budgets

    base = {"peak_live_bytes": 100000, "arg_bytes": 10, "out_bytes": 10,
            "collective_payload_bytes": 0}
    within = dict(base, peak_live_bytes=105000)   # +5% < 10% band
    beyond = dict(base, peak_live_bytes=125000)   # +25%
    assert compare_budgets("p", base, within) == []
    named = compare_budgets("p", base, beyond)
    assert len(named) == 1 and "peak_live_bytes" in named[0]
    # byte-exact metrics have no band
    comms = dict(base, collective_payload_bytes=4)
    assert any("collective_payload_bytes" in p
               for p in compare_budgets("p", base, comms))
    # a lock without a ledger is itself a named problem
    assert any("no budget ledger" in p
               for p in compare_budgets("p", None, base))


def test_version_and_geometry_skew_named(capture):
    """ISSUE 12 satellite: a lock generated under a different jax or
    device geometry fails with ONE named diagnostic, never a confusing
    per-program sha mismatch storm."""
    from tpudp.analysis import audit

    skewed = json.loads(json.dumps(capture))
    skewed["jax"] = "0.0.1-other"
    for name in skewed["programs"]:
        skewed["programs"][name]["fingerprint"] = "deadbeef"
    problems = audit.compare(skewed, capture)
    assert len(problems) == 1 and "jax version skew" in problems[0], \
        problems

    skewed = json.loads(json.dumps(capture))
    skewed["geometry"] = {"platform": "tpu", "devices": 4}
    for name in skewed["programs"]:
        skewed["programs"][name]["fingerprint"] = "deadbeef"
    problems = audit.compare(skewed, capture)
    assert len(problems) == 1 and "geometry skew" in problems[0], problems


# -- CLI ----------------------------------------------------------------


def test_protocol_cli_exit_codes(capsys):
    bad = os.path.join(FIXTURES, "bad_protocol_divergent_entry.py")
    good = os.path.join(FIXTURES, "good_protocol_divergent_entry.py")
    assert cli_main(["protocol", bad]) == 1
    out = capsys.readouterr().out
    assert "protocol-divergent-entry" in out
    assert cli_main(["protocol", good]) == 0
    out = capsys.readouterr().out
    assert "deadlock-free within bounds" in out  # model check ran
    assert cli_main(["protocol", "tpudp/no_such_dir"]) == 2


def test_budget_cli_table(capsys):
    assert cli_main(["budget", "--table"]) == 0
    out = capsys.readouterr().out
    assert "serve.decode_step@s2m32" in out
    assert "peak_live" in out


@pytest.mark.slow  # one full in-process capture (~7s)
def test_check_umbrella_composes(capsys):
    """`check` = lint + protocol + audit/budget with composed exit
    codes: clean tree exits 0 and reports every stage."""
    assert cli_main(["check"]) == 0
    out = capsys.readouterr().out
    for token in ("== lint ==", "== protocol ==", "== audit",
                  "lint=ok", "protocol=ok", "audit+budget=ok"):
        assert token in out, out


@pytest.mark.slow  # real subprocess pays the full jax import
def test_check_cli_nonzero_composes_with_pipefail(tmp_path):
    """A failing stage must propagate through `set -o pipefail` — the
    umbrella's exit code composes like the individual gates (ISSUE 12
    satellite).  A bogus lock makes the audit stage fail while lint
    and protocol stay green."""
    bad_lock = tmp_path / "lock.json"
    bad_lock.write_text("{}")
    proc = subprocess.run(
        ["bash", "-c",
         "set -o pipefail; "
         f"{sys.executable} -m tpudp.analysis check --lock "
         f"{bad_lock} | cat"],
        cwd=ROOT, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "audit+budget=FAIL(1)" in proc.stdout


def test_verify_paths_is_jax_free():
    """The protocol verifier must load and run without jax (same
    file-path-load contract as the linter)."""
    code = (
        "import importlib.util, sys, os\n"
        f"pkg = {os.path.join(ROOT, 'tpudp', 'analysis')!r}\n"
        "spec = importlib.util.spec_from_file_location(\n"
        "    '_a', os.path.join(pkg, '__init__.py'),\n"
        "    submodule_search_locations=[pkg])\n"
        "mod = importlib.util.module_from_spec(spec)\n"
        "sys.modules['_a'] = mod\n"
        "spec.loader.exec_module(mod)\n"
        "from _a.protocol import verify_paths\n"
        f"f, e = verify_paths(['tpudp'], {ROOT!r})\n"
        "assert 'jax' not in sys.modules, 'protocol verifier imported "
        "jax!'\n"
        "print(len(f), len(e))\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == ["0", "0"]
