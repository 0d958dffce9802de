"""The per-program resource ledger: static memory/comms budgets from a
pinned program's jaxpr.

The trace audit (PR 8) answers "did the program CHANGE?" — its sha256
fingerprint flips on any edit, but the diff says nothing about *what
got more expensive*.  This module walks the same ``jax.make_jaxpr``
capture and reduces it to the three quantities the upcoming serving
rungs (paged attention, TP serving) must not silently regress:

  * ``peak_live_bytes`` — peak simultaneously-live buffer bytes under a
    donation-aware liveness sweep: every equation's outputs are born at
    their definition and die after their last use; **donated** program
    inputs (the arena, the train state — the ``donate_argnums`` tables
    the use-after-donation rule mirrors) die at their last use too,
    while non-donated inputs (the weights among them) stay
    resident for the whole program, exactly as XLA's aliasing rules
    allow.  Equations carrying sub-jaxprs (scan/cond/pjit) contribute
    their own inner peak at their program point.
  * ``collective_payload_bytes`` — total bytes moved by collective
    primitives (psum/ppermute/all_gather/...), recursively through
    sub-jaxprs: the static comms-volume twin of the audit's ordered
    collective sequence.
  * ``arg_bytes`` / ``out_bytes`` — the program's I/O footprint (flat
    argument and result bytes), the coarse "how big is a call" canary.

The ledger is committed into ``tools/trace_lock.json`` per program
(under ``"budget"``) by ``audit --update`` and diffed by ``audit`` /
``python -m tpudp.analysis budget`` with per-program, per-metric deltas
named.  Byte metrics carry a tolerance band
(:data:`BUDGET_TOLERANCES`) so an intended small change does not thrash
the gate, while a doubled live buffer or a new collective fails loudly
with the program and metric in the message.

This is a *static* model, not a simulator: XLA's scheduler may overlap
or rematerialize differently on a real backend.  It is a deterministic
canary — the same jaxpr always produces the same ledger, so any delta
in the lock diff is a real change to the traced program.

Only the jax half of the package touches this module; imports stay
inside functions so the lint half remains stdlib-importable.
"""

from __future__ import annotations

#: Relative tolerance per budget metric: |new - old| / max(old, 1)
#: must stay within the band, else the audit fails naming the metric.
#: Byte-exact metrics use 0.0.
BUDGET_TOLERANCES = {
    "peak_live_bytes": 0.10,
    "arg_bytes": 0.0,
    "out_bytes": 0.0,
    "collective_payload_bytes": 0.0,
}

#: Primitives whose name marks a collective (same parts as the audit
#: census, duplicated here so this module imports standalone).
_COLLECTIVE_PARTS = ("psum", "pmax", "pmin", "ppermute", "pbroadcast",
                     "all_gather", "all_to_all", "reduce_scatter",
                     "pgather")

#: Wrapper primitives that pass their invars straight through to one
#: sub-jaxpr — unwrapped so a jitted step function's ledger reflects
#: the program body, not a single opaque call equation.
_WRAPPER_PRIMS = {"pjit", "closed_call", "core_call", "remat", "remat2",
                  "custom_jvp_call", "custom_vjp_call"}


def _aval_bytes(v) -> int:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    size = 1
    try:
        for d in shape:
            size *= int(d)
    except (TypeError, ValueError):  # symbolic dimension
        return 0
    return size * dtype.itemsize


def _sub_jaxprs(eqn):
    from jax.extend.core import Jaxpr

    for v in eqn.params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for sub in vs:
            if isinstance(sub, Jaxpr) or hasattr(sub, "jaxpr"):
                yield sub


def _unwrap(jaxpr, donated):
    """Descend through single-equation pass-through wrappers (a jitted
    function traces to one ``pjit`` eqn) so the ledger sees the real
    body.  The donated-invar index set survives because a wrapper's eqn
    invars are the outer invars in order."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    while len(inner.eqns) == 1:
        eqn = inner.eqns[0]
        if eqn.primitive.name not in _WRAPPER_PRIMS:
            break
        outer_vars = [v for v in eqn.invars if hasattr(v, "aval")]
        if len(outer_vars) != len(inner.invars) or any(
                a is not b for a, b in zip(outer_vars, inner.invars)):
            break
        subs = list(_sub_jaxprs(eqn))
        if len(subs) != 1:
            break
        inner = getattr(subs[0], "jaxpr", subs[0])
    return inner, donated


def _peak_live(jaxpr, donated=frozenset()) -> int:
    """Donation-aware liveness sweep over one (open) jaxpr level."""
    from jax.extend.core import Literal

    eqns = list(jaxpr.eqns)
    n = len(eqns)
    last_use: dict = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if not isinstance(v, Literal):
                last_use[v] = i
    for v in jaxpr.outvars:
        if not isinstance(v, Literal):
            last_use[v] = n  # program results outlive every eqn
    resident = 0  # live for the whole program
    dying: list = []  # (birth, death, bytes) intervals
    for v in getattr(jaxpr, "constvars", ()):
        resident += _aval_bytes(v)
    for idx, v in enumerate(jaxpr.invars):
        if idx in donated:
            dying.append((-1, last_use.get(v, -1), _aval_bytes(v)))
        else:
            resident += _aval_bytes(v)
    for i, eqn in enumerate(eqns):
        for v in eqn.outvars:
            dying.append((i, last_use.get(v, i), _aval_bytes(v)))
    inner_extra = [0] * max(n, 1)
    for i, eqn in enumerate(eqns):
        io = sum(_aval_bytes(v) for v in list(eqn.invars) + list(eqn.outvars)
                 if not isinstance(v, Literal))
        extra = 0
        for sub in _sub_jaxprs(eqn):
            extra += max(0, _peak_live(getattr(sub, "jaxpr", sub)) - io)
        inner_extra[i] = extra
    if n == 0:
        return resident + sum(b for _, _, b in dying)
    peak = 0
    for i in range(n):
        live = resident + inner_extra[i]
        for b, d, size in dying:
            if b <= i <= d:
                live += size
        peak = max(peak, live)
    return peak


def _collective_payload(jaxpr) -> int:
    total = 0
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in inner.eqns:
        name = eqn.primitive.name
        if any(p in name for p in _COLLECTIVE_PARTS):
            total += sum(_aval_bytes(v) for v in eqn.outvars)
        for sub in _sub_jaxprs(eqn):
            total += _collective_payload(sub)
    return total


def ledger(closed_jaxpr, donated=frozenset()) -> dict:
    """The budget record for one traced program.  ``donated`` holds the
    FLAT invar indices (pytree arguments flattened, the same order
    ``jax.make_jaxpr`` binds them) that the runtime donates."""
    from jax.extend.core import Literal

    inner, donated = _unwrap(closed_jaxpr, frozenset(donated))
    arg_bytes = sum(_aval_bytes(v) for v in inner.invars)
    out_bytes = sum(_aval_bytes(v) for v in inner.outvars
                    if not isinstance(v, Literal))
    return {
        "peak_live_bytes": _peak_live(inner, donated),
        "arg_bytes": arg_bytes,
        "out_bytes": out_bytes,
        "collective_payload_bytes": _collective_payload(inner),
    }


def donated_flat_indices(args, donate_argnums) -> frozenset[int]:
    """Map per-ARGUMENT donation indices (the runtime's
    ``donate_argnums``) to FLAT invar indices: each pytree argument
    occupies a contiguous run of leaves in the traced program's invars."""
    import jax

    flat: set[int] = set()
    offset = 0
    donate = set(donate_argnums)
    for i, arg in enumerate(args):
        n = len(jax.tree_util.tree_leaves(arg))
        if i in donate:
            flat.update(range(offset, offset + n))
        offset += n
    return frozenset(flat)


def compare_budgets(name: str, old: dict | None,
                    new: dict | None) -> list[str]:
    """Named per-metric deltas for one program, tolerance bands applied.
    Returns human-readable problem strings (empty = within budget)."""
    problems = []
    if new is None:
        return problems
    if old is None:
        return [f"{name}: no budget ledger in the lockfile — regenerate "
                f"with --update to pin peak-live/comms budgets"]
    for metric, tol in BUDGET_TOLERANCES.items():
        a, b = old.get(metric), new.get(metric)
        if a is None or b is None or a == b:
            continue
        rel = abs(b - a) / max(abs(a), 1)
        if rel <= tol:
            continue
        direction = "+" if b > a else "-"
        problems.append(
            f"{name}: budget metric {metric} {a} -> {b} "
            f"({direction}{rel * 100:.2g}%, tolerance {tol * 100:.0f}%) — "
            f"the program's static resource ledger regressed; if intended, "
            f"regenerate with --update and review the lockfile diff")
    return problems


def lock_has_ledgers(lock: dict) -> bool:
    """Is the committed lock budget-complete — capture geometry present
    and a ledger under every pinned program?  THE one definition,
    shared by `budget --table` and the tier-1 presence test (consumers
    that must never disagree about the same artifact).  Stdlib-only."""
    programs = lock.get("programs")
    return bool(lock.get("geometry") and programs
                and all("budget" in rec for rec in programs.values()))


def render_table(programs: dict) -> str:
    """A fixed-width ledger table for the ``budget`` subcommand."""
    rows = [("program", "peak_live", "args", "outs", "coll_payload")]
    for name in sorted(programs):
        b = programs[name].get("budget") or {}
        rows.append((name,
                     _human(b.get("peak_live_bytes")),
                     _human(b.get("arg_bytes")),
                     _human(b.get("out_bytes")),
                     _human(b.get("collective_payload_bytes"))))
    widths = [max(len(r[i]) for r in rows) for i in range(5)]
    return "\n".join(
        "  ".join(c.ljust(widths[i]) for i, c in enumerate(r)).rstrip()
        for r in rows)


def _human(n) -> str:
    if n is None:
        return "-"
    for unit in ("B", "KiB", "MiB"):
        if abs(n) < 1024:
            return f"{n:.1f}{unit}" if unit != "B" else f"{n}B"
        n /= 1024
    return f"{n:.1f}GiB"
