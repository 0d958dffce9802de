"""Rule engine for the tpudp hazard linter.

Pure stdlib (``ast`` + ``re``) by design: lint must run without jax
(in seconds, and on a host whose jax is absent or broken), so this
module and :mod:`tpudp.analysis.rules` never import anything heavier
than the standard library.  The jaxpr auditor
(:mod:`tpudp.analysis.audit`) is the only part of the package that
touches jax, and it does so lazily inside functions.

The engine parses each target file once, builds the shared per-module
facts every rule needs — a parent map, an import-alias table, and the
*traced-region index* (which function defs run under a jax trace) —
and hands the :class:`Module` to every registered rule.

Suppressions are explicit ``# tpudp: lint-ok(rule)`` comments, either
on the offending line or on a comment-only line directly above it; an
optional ``: reason`` tail documents why.  Every suppression must
*match* a finding — one that suppresses nothing is itself reported
(``useless-suppression``), so stale exceptions can't accumulate.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import os
import re
import tokenize

SUPPRESS_RE = re.compile(r"#\s*tpudp:\s*lint-ok\(([a-z0-9_\-,\s]+)\)")
MARKER_RE = re.compile(r"#\s*tpudp:\s*([a-z0-9\-]+)\b")

#: Rules owned by the protocol verifier (tpudp/analysis/protocol.py).
#: The lint pass and the protocol pass share one suppression syntax but
#: check different rule sets, so each pass reports useless suppressions
#: only for the names IT owns — a `lint-ok(protocol-*)` that matches
#: nothing is flagged by the protocol pass, a typo'd name that belongs
#: to neither is still flagged by lint.  Defined here (not in
#: protocol.py) to keep the import graph acyclic; protocol.py re-uses
#: this set and a test pins it against the shipped protocol rules.
PROTOCOL_RULE_NAMES = frozenset({
    "protocol-divergent-entry",
    "protocol-order-divergence",
    "protocol-early-exit",
    "protocol-divergent-loop",
})

#: The multihost modules the protocol verifier covers by default:
#: everywhere a cross-process rendezvous is issued or decided.  Files
#: outside this scope (and without a ``# tpudp: protocol-module``
#: marker) are never verified, so lint must NOT defer their
#: protocol-rule suppressions — a stale `lint-ok(protocol-*)` in an
#: out-of-scope file would otherwise be flagged by neither pass.
#: Defined here (not in protocol.py) so lint can make that scope
#: decision without a circular import; protocol.py re-exports it.
PROTOCOL_MODULES = (
    "tpudp/resilience.py",
    "tpudp/utils/checkpoint.py",
    "tpudp/utils/consistency.py",
    "tpudp/mesh.py",
    "tpudp/cli.py",
    "tpudp/train.py",
    "tpudp/serve/engine.py",
    "tpudp/serve/disagg.py",
    "tpudp/obs/flight.py",
)


def in_protocol_scope(rel: str, markers: set[str]) -> bool:
    """Is this file one the protocol verifier analyzes?  By configured
    module path, or by an explicit first-lines marker."""
    rel = rel.replace(os.sep, "/")
    return ("protocol-module" in markers
            or any(rel.endswith(m) for m in PROTOCOL_MODULES))

#: Attribute reads that yield *static* (host, trace-time-constant)
#: values even on traced arrays — branching or syncing on these is fine.
STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding", "aval",
                "nbytes", "itemsize"}

#: Builtin calls whose result is static/host regardless of arguments.
#: float/int/bool belong here for TAINT purposes: applied to a device
#: value they are themselves the sync (the host-sync rule flags the
#: call), and their result is a host scalar — downstream reads are
#: clean.
STATIC_CALLS = {"len", "isinstance", "type", "getattr", "hasattr",
                "range", "id", "repr", "str", "format",
                "float", "int", "bool", "complex"}

#: Decorator / higher-order entry points that put a function under a
#: jax trace.  Dotted names are post-alias-resolution (``from jax
#: import lax`` resolves to ``jax.lax``).
TRACING_ENTRY_POINTS = {
    "jax.jit", "jax.pjit", "jax.shard_map", "jax.vmap", "jax.pmap",
    "jax.grad", "jax.value_and_grad", "jax.checkpoint", "jax.remat",
    "jax.make_jaxpr", "jax.eval_shape", "jax.lax.scan", "jax.lax.cond",
    "jax.lax.while_loop", "jax.lax.switch", "jax.lax.map",
    "jax.lax.associative_scan", "jax.lax.custom_root",
    "jax.experimental.shard_map.shard_map", "jax.custom_jvp",
    "jax.custom_vjp",
}


@dataclasses.dataclass(frozen=True)
class Finding:
    """One lint hit, pointing at a concrete source location."""

    rule: str
    path: str          # repo-relative, posix separators
    line: int
    col: int
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule}: " \
               f"{self.message}"


def comment_tokens(source: str) -> dict[int, str]:
    """line → comment text, from real COMMENT tokens only (a docstring
    that merely *mentions* ``# tpudp: lint-ok(...)`` must not count)."""
    out: dict[int, str] = {}
    try:
        for tok in tokenize.generate_tokens(io.StringIO(source).readline):
            if tok.type == tokenize.COMMENT:
                out[tok.start[0]] = tok.string
    except (tokenize.TokenError, IndentationError, SyntaxError):
        pass
    return out


class Suppressions:
    """``# tpudp: lint-ok(rule[, rule...])`` comments for one file.

    A comment on a code line covers that line; a suppression inside a
    comment block covers the next *code* line after the block (so the
    justification can span several comment lines).  :meth:`allows`
    records use so :meth:`unused` can report suppressions that matched
    nothing.
    """

    def __init__(self, source: str, comments: dict[int, str] | None = None):
        self._cover: dict[int, list[tuple[int, str]]] = {}
        self._declared: list[tuple[int, str]] = []
        self._used: set[tuple[int, str]] = set()
        if comments is None:
            comments = comment_tokens(source)
        lines = source.splitlines()

        def _comment_or_blank(n: int) -> bool:
            if n > len(lines):
                return False
            stripped = lines[n - 1].strip()
            return not stripped or stripped.startswith("#")

        for lineno, text in comments.items():
            m = SUPPRESS_RE.search(text)
            if not m:
                continue
            code = lines[lineno - 1] if lineno <= len(lines) else ""
            target = lineno
            if code.lstrip().startswith("#"):
                target = lineno + 1
                while target <= len(lines) and _comment_or_blank(target):
                    target += 1
            for rule in m.group(1).split(","):
                rule = rule.strip()
                if rule:
                    self._declared.append((lineno, rule))
                    self._cover.setdefault(target, []).append((lineno, rule))

    def allows(self, line: int, rule: str) -> bool:
        for decl_line, r in self._cover.get(line, ()):
            if r == rule:
                self._used.add((decl_line, r))
                return True
        return False

    def unused(self) -> list[tuple[int, str]]:
        return [(line, rule) for line, rule in self._declared
                if (line, rule) not in self._used]


class Module:
    """One parsed file plus the shared facts rules consume."""

    def __init__(self, path: str, rel: str, source: str):
        self.path = path
        self.rel = rel.replace(os.sep, "/")
        self.source = source
        self.tree = ast.parse(source)
        self.lines = source.splitlines()
        self.comments = comment_tokens(source)
        self.suppressions = Suppressions(source, self.comments)
        self.markers = {m.group(1)
                        for line, text in self.comments.items() if line <= 5
                        for m in [MARKER_RE.search(text)] if m}
        self.parents: dict[ast.AST, ast.AST] = {}
        for parent in ast.walk(self.tree):
            for child in ast.iter_child_nodes(parent):
                self.parents[child] = parent
        self.imports = self._import_aliases()
        self.functions = self._collect_functions()
        self.traced = self._traced_index()

    # -- imports -------------------------------------------------------

    def _import_aliases(self) -> dict[str, str]:
        aliases: dict[str, str] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    aliases[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0])
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    aliases[a.asname or a.name] = f"{node.module}.{a.name}"
        return aliases

    def dotted(self, node: ast.AST) -> str | None:
        """Dotted path of a Name/Attribute chain with the root resolved
        through the module's import aliases (``np.random`` →
        ``numpy.random``); None for anything else (calls, subscripts)."""
        parts: list[str] = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        root = self.imports.get(node.id, node.id)
        parts.append(root)
        return ".".join(reversed(parts))

    def raw_dotted(self, node: ast.AST) -> str | None:
        """Dotted path WITHOUT alias resolution (``self.state.params``)
        — the spelling taint tracking keys on."""
        parts: list[str] = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        parts.append(node.id)
        return ".".join(reversed(parts))

    # -- function index ------------------------------------------------

    def _collect_functions(self) -> dict[ast.FunctionDef, str]:
        """Every def, mapped to its dotted qualname (``Engine.step``,
        ``make_train_step.train_step``)."""
        out: dict[ast.FunctionDef, str] = {}

        def visit(node, prefix):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    qual = f"{prefix}{child.name}"
                    out[child] = qual
                    visit(child, qual + ".")
                elif isinstance(child, ast.ClassDef):
                    visit(child, f"{prefix}{child.name}.")
                else:
                    visit(child, prefix)

        visit(self.tree, "")
        return out

    def enclosing_function(self, node: ast.AST):
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return cur
            cur = self.parents.get(cur)
        return None

    # -- traced-region index -------------------------------------------

    def _jit_decorator_info(self, fn) -> tuple[bool, set[str], tuple]:
        """(is_jit_rooted, static param names, donated indices) from the
        def's decorators."""
        static: set[str] = set()
        donated: tuple = ()
        rooted = False
        for dec in fn.decorator_list:
            call = dec if isinstance(dec, ast.Call) else None
            target = call.func if call else dec
            dotted = self.dotted(target)
            if dotted in TRACING_ENTRY_POINTS:
                rooted = True
            elif (dotted in ("functools.partial", "partial") and call
                    and call.args
                    and self.dotted(call.args[0]) in TRACING_ENTRY_POINTS):
                rooted = True
            else:
                continue
            kwargs = call.keywords if call else []
            for kw in kwargs:
                if kw.arg in ("static_argnames", "static_argnums"):
                    try:
                        val = ast.literal_eval(kw.value)
                    except ValueError:
                        continue
                    vals = val if isinstance(val, (tuple, list)) else (val,)
                    args = [a.arg for a in fn.args.posonlyargs + fn.args.args]
                    for v in vals:
                        if isinstance(v, str):
                            static.add(v)
                        elif isinstance(v, int) and v < len(args):
                            static.add(args[v])
                if kw.arg == "donate_argnums":
                    try:
                        val = ast.literal_eval(kw.value)
                    except ValueError:
                        continue
                    donated = tuple(val) if isinstance(
                        val, (tuple, list)) else (val,)
        return rooted, static, donated

    def _traced_index(self) -> dict[ast.FunctionDef, str]:
        """def → how it gets traced: 'root' (jit/partial(jax.jit)
        decorator), 'combinator' (passed to lax.scan/cond/shard_map/...),
        'nested' (defined inside a traced def), or 'transitive' (called
        from a traced def in this module)."""
        traced: dict[ast.FunctionDef, str] = {}
        by_name: dict[str, list[ast.FunctionDef]] = {}
        for fn in self.functions:
            by_name.setdefault(fn.name, []).append(fn)

        for fn in self.functions:
            rooted, _, _ = self._jit_decorator_info(fn)
            if rooted:
                traced[fn] = "root"

        # defs passed (by name) to tracing combinators, incl.
        # ``step = jax.jit(step_fn)`` call forms.
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            dotted = self.dotted(node.func)
            if dotted in ("functools.partial", "partial") and node.args:
                dotted = self.dotted(node.args[0])
                cands = node.args[1:]
            else:
                cands = list(node.args)
            if dotted not in TRACING_ENTRY_POINTS:
                continue
            for arg in cands:
                if isinstance(arg, ast.Name):
                    for fn in by_name.get(arg.id, ()):
                        traced.setdefault(fn, "combinator")

        # closure: nested defs + same-module callees of traced defs
        changed = True
        while changed:
            changed = False
            for fn in self.functions:
                if fn in traced:
                    continue
                parent = self.enclosing_function(fn)
                if parent is not None and parent in traced:
                    traced[fn] = "nested"
                    changed = True
            for fn, kind in list(traced.items()):
                for node in ast.walk(fn):
                    if isinstance(node, ast.Call):
                        name = None
                        if isinstance(node.func, ast.Name):
                            name = node.func.id
                        elif (isinstance(node.func, ast.Attribute)
                                and isinstance(node.func.value, ast.Name)
                                and node.func.value.id == "self"):
                            name = node.func.attr
                        if name:
                            for callee in by_name.get(name, ()):
                                if callee not in traced and callee is not fn:
                                    traced[callee] = "transitive"
                                    changed = True
        return traced

    def traced_kind(self, node: ast.AST) -> str | None:
        """'root'/'combinator'/'nested'/'transitive' if ``node`` sits
        inside a traced def, else None."""
        fn = node if isinstance(node, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)) \
            else self.enclosing_function(node)
        while fn is not None:
            kind = self.traced.get(fn)
            if kind is not None:
                return kind
            fn = self.enclosing_function(fn)
        return None

    def traced_params(self, fn) -> set[str]:
        """Param names of a directly-traced def that are traced values
        (non-static).  Empty for untraced/transitively-traced defs."""
        if self.traced.get(fn) not in ("root", "combinator", "nested"):
            return set()
        _, static, _ = self._jit_decorator_info(fn)
        names = {a.arg for a in fn.args.posonlyargs + fn.args.args
                 + fn.args.kwonlyargs}
        names.discard("self")
        names.discard("cls")
        return names - static


def mentions(mod: Module, node: ast.AST, tainted: set[str]) -> bool:
    """Does ``node`` evaluate through a tainted value?

    ``tainted`` holds raw dotted paths ("x", "self.state").  Static
    attribute reads (``x.shape``), identity tests (``x is None``) and
    host builtins (``len``, ``isinstance``) break the taint.
    """
    if isinstance(node, ast.Name) or isinstance(node, ast.Attribute):
        if isinstance(node, ast.Attribute) and node.attr in STATIC_ATTRS:
            return False
        dotted = mod.raw_dotted(node)
        if dotted is not None:
            for t in tainted:
                if dotted == t or dotted.startswith(t + "."):
                    return True
            return False
        if isinstance(node, ast.Attribute):
            return mentions(mod, node.value, tainted)
        return False
    if isinstance(node, ast.Call):
        fn_dotted = mod.dotted(node.func)
        if fn_dotted in STATIC_CALLS:
            return False
        parts = [*node.args, *[kw.value for kw in node.keywords]]
        if isinstance(node.func, ast.Attribute):
            parts.append(node.func.value)
        return any(mentions(mod, p, tainted) for p in parts)
    if isinstance(node, ast.Compare):
        if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
            return False
        return any(mentions(mod, c, tainted)
                   for c in [node.left, *node.comparators])
    if isinstance(node, ast.Constant):
        return False
    return any(mentions(mod, c, tainted) for c in ast.iter_child_nodes(node))


def ordered_walk(fn: ast.AST, skip_nested_defs: bool = True):
    """Nodes of ``fn`` in source order (lineno, col) — ``ast.walk`` is
    breadth-first, which breaks linear taint propagation through nested
    blocks.  With ``skip_nested_defs``, bodies of defs nested inside
    ``fn`` are excluded (they are analyzed on their own)."""
    skip: set[int] = set()
    if skip_nested_defs:
        for node in ast.walk(fn):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)) and node is not fn:
                skip.update(id(n) for n in ast.walk(node))
                skip.discard(id(node))
    return sorted(
        (n for n in ast.walk(fn)
         if hasattr(n, "lineno") and id(n) not in skip),
        key=lambda n: (n.lineno, n.col_offset))


class Rule:
    """Base class: subclasses set ``name``/``summary`` and implement
    :meth:`check` yielding Findings (pre-suppression)."""

    name: str = ""
    summary: str = ""

    def check(self, mod: Module):
        raise NotImplementedError

    def finding(self, mod: Module, node: ast.AST, message: str) -> Finding:
        return Finding(self.name, mod.rel, getattr(node, "lineno", 1),
                       getattr(node, "col_offset", 0), message)


def iter_python_files(paths: list[str], root: str):
    """Yield (abspath, relpath) for every .py under the given paths."""
    skip_dirs = {"__pycache__", ".git", "bench_results", "node_modules",
                 ".venv"}
    for p in paths:
        ap = p if os.path.isabs(p) else os.path.join(root, p)
        if os.path.isfile(ap):
            yield ap, os.path.relpath(ap, root)
            continue
        for dirpath, dirnames, filenames in os.walk(ap):
            dirnames[:] = sorted(d for d in dirnames if d not in skip_dirs)
            for f in sorted(filenames):
                if f.endswith(".py"):
                    full = os.path.join(dirpath, f)
                    yield full, os.path.relpath(full, root)


def lint_paths(paths: list[str], root: str, rules=None,
               report_useless: bool = True):
    """Run every rule over every file; returns (findings, errors).

    ``findings`` excludes suppressed hits but includes a
    ``useless-suppression`` finding for each suppression that matched
    nothing.  ``errors`` are files that failed to parse (reported, not
    fatal — a syntax error is pytest/ruff's job).
    """
    if rules is None:
        from .rules import RULES
        rules = RULES
    findings: list[Finding] = []
    errors: list[str] = []
    for path, rel in iter_python_files(paths, root):
        try:
            with open(path, encoding="utf-8") as f:
                source = f.read()
            mod = Module(path, rel, source)
        except (SyntaxError, UnicodeDecodeError) as exc:
            errors.append(f"{rel}: parse failed: {exc}")
            continue
        for rule in rules:
            for finding in rule.check(mod):
                if not mod.suppressions.allows(finding.line, rule.name):
                    findings.append(finding)
        if report_useless:
            in_protocol = in_protocol_scope(mod.rel, mod.markers)
            for line, rule_name in mod.suppressions.unused():
                if rule_name in PROTOCOL_RULE_NAMES and in_protocol:
                    continue  # the protocol pass owns these names HERE;
                    # out of its scope nothing would ever report them
                findings.append(Finding(
                    "useless-suppression", mod.rel, line, 0,
                    f"lint-ok({rule_name}) suppresses nothing — remove it "
                    f"(or the hazard it excused is gone)"))
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings, errors
