"""tpudp.analysis — static enforcement of the repo's runtime invariants.

Two surfaces (docs/ANALYSIS.md):

  * ``python -m tpudp.analysis lint`` — an AST-based, repo-aware linter
    for the failure classes this codebase has already paid for:
    nondeterminism baked into traces, Python branches on traced values,
    host syncs on scheduler hot paths, use-after-donation, collectives
    under per-host-divergent control flow, and unobservable jit
    programs.  Suppressions are explicit ``# tpudp: lint-ok(rule)``
    comments, so every sanctioned exception is visible in a diff.
  * ``python -m tpudp.analysis audit`` — traces the registered step
    programs at pinned CPU smoke geometries, fingerprints their jaxprs
    (plus a host-callback/transfer/collective census) and diffs against
    the committed ``tools/trace_lock.json``: a PR that introduces a
    recompile, a new host transfer, or a changed collective sequence in
    a hot path fails tier-1 loudly instead of silently regressing the
    benches.

This ``__init__`` (and the lint half of the package) is import-light by
design — stdlib only, jax loaded lazily inside the audit functions —
because lint must run without jax: the lint and protocol gates take
seconds on a host with no jax at all, and a broken jax install must not
hide a lint finding.
"""

# Relative imports throughout the package: it loads standalone (by file
# path, under a synthetic package name), so the lint gate runs without
# importing the jax-heavy `tpudp` parent package
# (tests/test_analysis.py::test_sources_stale_is_jax_free_and_detects).
from .core import (PROTOCOL_RULE_NAMES, Finding, Module,  # noqa: F401
                   Rule, lint_paths)
from .protocol import (MigrationSpec, VoteSpec,  # noqa: F401
                       explore_migration_machine, explore_vote_machine,
                       extract_migration_spec, extract_vote_spec)
from .protocol import verify_paths as verify_protocol  # noqa: F401
from .rules import RULES, RULES_BY_NAME  # noqa: F401
