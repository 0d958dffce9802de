"""Trace-stability auditor: jaxpr fingerprints + transfer census vs a
committed lockfile.

For every program in :mod:`tpudp.analysis.programs` this traces the
function (``jax.make_jaxpr`` — trace only, nothing compiles) and
records:

  * ``fingerprint`` — sha256 of the canonicalized jaxpr text (memory
    addresses scrubbed).  Any change to the traced computation —
    including one that would force a recompile at fixed shapes —
    changes it.
  * ``collectives`` — the ordered sequence of collective primitives
    (psum/ppermute/all_gather/...), recursively through scan/cond/pjit
    sub-jaxprs.  This is the static twin of PR 7's runtime vote: two
    hosts tracing different collective sequences deadlock a pod.
  * ``callbacks`` / ``transfers`` — host-callback and device_put
    primitive counts: a new host round trip inside a step program is a
    latency regression serve_bench would only catch after the fact.
  * ``eqns`` — total equation count (a coarse program-size canary).

``compare`` diffs a capture against the lockfile and names the
offending program and WHAT changed.  Source digests (sha256 of
AUDIT_SOURCES) also ride in the lock so the stdlib half below
(``sources_stale``) can flag a stale lock without importing jax; the
tier-1 test keeps them fresh, so every hot-path edit forces an
explicit ``audit --update`` + lockfile diff in review.

Module import is jax-free; jax loads inside the functions.
"""

from __future__ import annotations

import hashlib
import json
import os
import re

#: Bumped whenever the lock schema changes shape (v2: per-program
#: "budget" ledgers + top-level "geometry"), so an old committed lock
#: fails with the version diagnostic and its --update advice instead
#: of a misleading field-level mismatch.
LOCK_VERSION = 2

#: Substrings identifying collective primitives (matched against
#: primitive names so jax renames like psum→psum2 keep being counted
#: — the recorded name is always the real one).
COLLECTIVE_PRIM_PARTS = ("psum", "pmax", "pmin", "ppermute", "pbroadcast",
                         "all_gather", "all_to_all", "reduce_scatter",
                         "pgather")
CALLBACK_PRIM_PARTS = ("callback",)
TRANSFER_PRIM_NAMES = {"device_put", "copy"}

_ADDR_RE = re.compile(r"0x[0-9a-fA-F]+")
# jax 0.9.0 prints set-valued eqn params (shard_map's ``manual_axes=
# frozenset({'pipe', 'data'})``) in hash order, which PYTHONHASHSEED
# reshuffles per process — sort the elements so the fingerprint is a
# function of the program alone.
_SET_RE = re.compile(r"frozenset\(\{([^{}]*)\}\)")


def _stable_text(closed_jaxpr) -> str:
    text = _ADDR_RE.sub("0xX", str(closed_jaxpr))
    return _SET_RE.sub(
        lambda m: "frozenset({%s})" % ", ".join(
            sorted(e.strip() for e in m.group(1).split(","))), text)


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


# -- stdlib half (jax-free) --------------------------------------------

def source_digests(root: str | None = None) -> dict[str, str]:
    from .programs import AUDIT_SOURCES

    root = root or repo_root()
    out = {}
    for rel in AUDIT_SOURCES:
        path = os.path.join(root, rel)
        h = hashlib.sha256()
        try:
            with open(path, "rb") as f:
                h.update(f.read())
            out[rel] = h.hexdigest()
        except OSError:
            out[rel] = "MISSING"
    return out


def load_lock(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def write_lock(path: str, capture_result: dict) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(capture_result, f, indent=1, sort_keys=True)
        f.write("\n")


def sources_stale(lock_path: str, root: str | None = None) -> list[str]:
    """Pinned source files whose digest no longer matches the lock —
    pure stdlib, usable where jax is not.  A missing/
    unreadable lock returns every pinned source."""
    try:
        lock = load_lock(lock_path)
    except (OSError, json.JSONDecodeError):
        from .programs import AUDIT_SOURCES
        return list(AUDIT_SOURCES)
    recorded = lock.get("sources", {})
    current = source_digests(root)
    return sorted(set(
        [rel for rel, digest in current.items()
         if recorded.get(rel) != digest]
        + [rel for rel in recorded if rel not in current]))


# -- jax half ----------------------------------------------------------

def force_smoke_backend():
    """Pin the CPU backend with 8 virtual devices BEFORE first use, so
    the audit geometry is identical on every host (laptop, CI, TPU VM).
    Raises RuntimeError if another backend already initialized."""
    flag = "--xla_force_host_platform_device_count=8"
    if flag not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + flag)
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        pass  # backend already up — verified below
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            "the trace audit must run on the CPU smoke backend, but "
            f"backend {jax.default_backend()!r} is already initialized — "
            "run `python -m tpudp.analysis audit` in a fresh process")
    if jax.device_count() < 8:
        raise RuntimeError(
            "the trace audit needs >= 8 virtual CPU devices for the mesh "
            "geometries; launch with XLA_FLAGS="
            "--xla_force_host_platform_device_count=8 (a fresh "
            "`python -m tpudp.analysis audit` sets this itself)")
    return jax


def _census(jaxpr, acc) -> None:
    from jax.extend.core import Jaxpr

    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in inner.eqns:
        name = eqn.primitive.name
        acc["eqns"] += 1
        if any(p in name for p in COLLECTIVE_PRIM_PARTS):
            acc["collectives"].append(name)
        if any(p in name for p in CALLBACK_PRIM_PARTS):
            acc["callbacks"] += 1
        if name in TRANSFER_PRIM_NAMES:
            acc["transfers"] += 1
        for v in eqn.params.values():
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for sub in vs:
                if isinstance(sub, Jaxpr) or hasattr(sub, "jaxpr"):
                    _census(sub, acc)


def fingerprint(fn, args, donate_argnums=()) -> dict:
    """Trace ``fn(*args)`` and reduce the jaxpr to its lock record —
    fingerprint + census + the static resource ledger
    (:mod:`tpudp.analysis.budget`, donation-aware via
    ``donate_argnums``)."""
    import jax

    from . import budget as _budget

    closed = jax.make_jaxpr(fn)(*args)
    text = _stable_text(closed)
    acc = {"eqns": 0, "collectives": [], "callbacks": 0, "transfers": 0}
    _census(closed, acc)
    return {
        "fingerprint": hashlib.sha256(text.encode()).hexdigest(),
        "eqns": acc["eqns"],
        "collectives": acc["collectives"],
        "callbacks": acc["callbacks"],
        "transfers": acc["transfers"],
        "budget": _budget.ledger(
            closed, _budget.donated_flat_indices(args, donate_argnums)),
    }


def geometry() -> dict:
    """The capture environment's identity: the lock is only comparable
    under the same jax backend/device-count (the audit pins cpu+8
    virtual devices precisely so this never varies between hosts)."""
    import jax

    return {"platform": jax.default_backend(),
            "devices": jax.device_count()}


def capture(programs: dict | None = None) -> dict:
    """Trace every registered program → a lockfile-shaped dict."""
    import jax

    from .programs import PROGRAM_DONATIONS

    if programs is None:
        from .programs import build_programs
        programs = build_programs()
    return {
        "version": LOCK_VERSION,
        "jax": jax.__version__,
        "geometry": geometry(),
        "programs": {
            name: fingerprint(
                fn, args,
                PROGRAM_DONATIONS.get(name.split("@")[0], ()))
            for name, (fn, args) in programs.items()},
        "sources": source_digests(),
    }


def identity_skew(lock: dict, current: dict) -> list[str]:
    """NAMED version/geometry-skew diagnostics, checked BEFORE any
    per-program diff: a different jax re-prints every jaxpr (and a
    different device count re-derives every ledger), so reporting that
    as thirteen per-program mismatches would bury the one actual
    cause.  Shared by ``compare`` and the ``budget`` subcommand — any
    consumer diffing lock records against a live capture must gate on
    this first."""
    problems: list[str] = []
    if lock.get("jax") != current.get("jax"):
        problems.append(
            f"jax version skew: lock was generated under jax "
            f"{lock.get('jax')}, this environment runs "
            f"{current.get('jax')} — jaxpr text is only comparable "
            f"within one jax version; regenerate with --update under "
            f"the pinned toolchain")
    elif lock.get("geometry") != current.get("geometry"):
        problems.append(
            f"capture geometry skew: lock was generated on "
            f"{lock.get('geometry')}, this capture ran on "
            f"{current.get('geometry')} — device count/backend are part "
            f"of the lock identity (the audit pins cpu+8 virtual "
            f"devices); rerun `python -m tpudp.analysis audit` in a "
            f"fresh process, or --update if the pinned geometry changed")
    return problems


def compare(lock: dict, current: dict) -> list[str]:
    """Human-readable mismatches, each naming the offending program."""
    problems: list[str] = []
    if lock.get("version") != current["version"]:
        problems.append(
            f"lock version {lock.get('version')} != auditor version "
            f"{current['version']} — regenerate with --update")
        return problems
    skew = identity_skew(lock, current)
    if skew:
        problems.extend(skew)
        return problems
    locked = lock.get("programs", {})
    live = current["programs"]
    for name in locked:
        if name not in live:
            problems.append(
                f"{name}: in the lockfile but no longer registered — a "
                f"pinned hot-path program disappeared (deliberate removal "
                f"=> --update)")
    for name, rec in live.items():
        old = locked.get(name)
        if old is None:
            problems.append(
                f"{name}: registered but not in the lockfile — run "
                f"--update to pin the new program")
            continue
        if old == rec:
            continue
        deltas = []
        if old.get("collectives") != rec["collectives"]:
            deltas.append(
                f"collective sequence changed: {old.get('collectives')} "
                f"-> {rec['collectives']} (host-uniform ordering is the "
                f"pod-deadlock invariant)")
        if old.get("callbacks") != rec["callbacks"]:
            deltas.append(
                f"host callbacks {old.get('callbacks')} -> "
                f"{rec['callbacks']} (a new host round trip inside the "
                f"step program)")
        if old.get("transfers") != rec["transfers"]:
            deltas.append(f"device transfers {old.get('transfers')} -> "
                          f"{rec['transfers']}")
        if old.get("eqns") != rec["eqns"]:
            deltas.append(f"eqn count {old.get('eqns')} -> {rec['eqns']}")
        from . import budget as _budget

        budget_problems = _budget.compare_budgets(
            name, old.get("budget"), rec.get("budget"))
        deltas.extend(p.split(": ", 1)[1] for p in budget_problems)
        if not deltas:
            if old.get("fingerprint") == rec.get("fingerprint"):
                # identical trace, differing record fields that cleared
                # their tolerance bands (e.g. a donation-table edit
                # re-derived peak_live_bytes within ±10%) — the lock is
                # stale, not the math
                deltas.append(
                    "record fields changed within tolerance bands "
                    "(budget ledger re-derived under new donation "
                    "facts?) — the trace itself is identical; "
                    "regenerate with --update to refresh the lock")
            else:
                deltas.append("jaxpr fingerprint changed at identical "
                              "census — the traced math itself differs")
        problems.append(f"{name}: trace changed — " + "; ".join(deltas))
    cur_sources = current.get("sources", {})
    lock_sources = lock.get("sources", {})
    stale = sorted(
        {rel for rel, digest in cur_sources.items()
         if lock_sources.get(rel) != digest}
        # symmetric: a file REMOVED from AUDIT_SOURCES (or renamed)
        # without --update leaves a rotted lock entry — same staleness
        | {rel for rel in lock_sources if rel not in cur_sources})
    if stale:
        problems.append(
            "stale source digests (edit without --update): "
            + ", ".join(stale)
            + " — traces still match, but the lock's provenance is out "
              "of date; rerun `python -m tpudp.analysis audit --update` "
              "and commit the lockfile")
    return problems
