"""The tpudp hazard rules — one per failure class this repo has paid for.

Each rule documents, in its ``summary``/docstring, the runtime failure
it front-runs; docs/ANALYSIS.md carries a before/after example per
rule.  Repo knowledge (which functions are scheduler hot paths, which
callables donate which arguments) lives in the config tables below so
the linter enforces the invariants even when a file stops advertising
them; fixture files opt in through markers instead
(``# tpudp: hot-path`` on the def line or the line above it,
``# tpudp: compile-once-module`` / ``# tpudp: collective-module`` in
the file's first lines).

Rules are pure stdlib — see tpudp/analysis/core.py.
"""

from __future__ import annotations

import ast
import re

from .core import Module, Rule, mentions, ordered_walk

# -- repo-aware configuration -----------------------------------------

#: Scheduler/step hot paths: host code on the per-token / per-step
#: critical path, where an unannounced host sync is a latency
#: regression (ROADMAP "kill the per-token host round-trip").  Keyed by
#: repo-relative path → def qualnames.
HOT_PATHS = {
    "tpudp/serve/engine.py": {
        "Engine.step", "Engine._run_prefill_chunk", "Engine._run_decode",
        "Engine._run_decode_fused", "Engine._run_verify",
        "Engine._run_spec_fused", "Engine._run_verify_tree",
        "Engine._gather_drafts", "Engine._gather_tree_drafts",
        "Engine._commit",
    },
    "tpudp/train.py": {
        "Trainer.train_epoch", "Trainer.evaluate",
    },
}

#: Dotted roots that are device-resident state inside hot paths (taint
#: seeds for the host-sync rule), per file.
DEVICE_ROOTS = {
    "tpudp/serve/engine.py": {"self._keys"},
    "tpudp/train.py": {"self.state"},
}

#: Calls whose results are device values (taint seeds): the engine/
#: trainer step-program attributes plus anything reached through the
#: fault-seam wrapper ``self._device(kind, fn, *args)``.
DEVICE_CALL_ATTRS = {
    "_device", "train_step", "eval_step", "fwd_step", "decode_step",
    "verify_step", "prefill_step", "fused_step", "decode_paged",
    "verify_paged", "prefill_paged", "fused_paged", "fused_spec_step",
    "fused_spec_paged", "tree_step", "tree_paged", "copy_block_in",
    "copy_block_out", "_sample_row",
}

#: Known donating callables (attribute or bare name) → donated
#: positional indices.  Mirrors the ``donate_argnums`` at their build
#: sites; locally-defined jit functions are additionally discovered
#: from their own decorators.  The second index on the serve step
#: programs is the OBS_DEVICE_COUNTERS accumulator (tpudp.obs) — tiny,
#: but donated like the arena, so a read of the stale counters buffer
#: after a step is the same class of bug as a stale-cache read.
DONATING = {
    "decode_step": (0, 8), "verify_step": (0, 9), "prefill_step": (0,),
    "fused_step": (0, 11), "train_step": (0,), "copy_block_in": (0,),
    "copy_block_out": (1,),
    # Paged twins (Engine(kv_pages=N)): the shared page pool donates in
    # the dense arena's place (the block table never does — it is
    # host-authoritative and uploaded per call).
    "decode_paged": (0, 9), "verify_paged": (0, 10),
    "prefill_paged": (0,), "fused_paged": (0, 12),
    # On-device speculation (ISSUE 16): the fused speculative window and
    # the tree-verify window donate the target arena/pool + the obs
    # counters; the draft model's KV is carry-local scratch, never an
    # argument, so it has no donation row.
    "fused_spec_step": (0, 12), "fused_spec_paged": (0, 13),
    "tree_step": (0, 9), "tree_paged": (0, 10),
}

#: Pass-through wrappers: ``self._device("kind", fn, *args)`` runs
#: ``fn(*args)`` — the donating callee sits at arg 1, its args start
#: at 2.
DEVICE_WRAPPERS = {"_device": (1, 2)}

#: Modules whose jitted programs must bump TRACE_COUNTS (the serve
#: compile-once discipline); fixtures opt in with
#: ``# tpudp: compile-once-module``.
COMPILE_ONCE_PREFIXES = ("tpudp/serve/",)

#: Modules whose Pallas kernels must belong to a pinned trace-audit
#: program family: every ``pl.pallas_call`` site must sit inside a
#: program that bumps TRACE_COUNTS itself, or inside a wrapper marked
#: ``# tpudp: kernel-program(<name>)`` where <name> is a registered
#: program (tpudp/analysis/programs.py TRACE_COUNTER_PROGRAMS values).
#: The training-side flash/ring kernels are deliberately OUT of scope —
#: they sit behind explicit attn_impl opt-ins, not the serving hot
#: path's default dispatch.  Fixtures opt in with
#: ``# tpudp: kernel-module``.
KERNEL_SCOPE_PREFIXES = ("tpudp/serve/", "tpudp/ops/paged_attention.py")

KERNEL_PROGRAM_RE = re.compile(r"#\s*tpudp:\s*kernel-program\(([\w.\-]+)\)")

#: Modules where host-side ordering feeds collectives/checkpoint
#: protocols, so unordered filesystem listings are a cross-host
#: divergence hazard; fixtures opt in with ``# tpudp: collective-module``.
COLLECTIVE_MODULE_PREFIXES = (
    "tpudp/parallel/", "tpudp/resilience.py", "tpudp/mesh.py",
    "tpudp/utils/consistency.py", "tpudp/utils/checkpoint.py",
)

#: lax collectives (post-alias-resolution dotted names).
COLLECTIVE_CALLS = {
    "jax.lax.psum", "jax.lax.pmean", "jax.lax.pmax", "jax.lax.pmin",
    "jax.lax.ppermute", "jax.lax.pshuffle", "jax.lax.all_gather",
    "jax.lax.all_to_all", "jax.lax.psum_scatter",
}

#: Repo/runtime cross-process protocol helpers — every host must reach
#: these together (matched by terminal name).
COLLECTIVE_HELPERS = {
    "gather_host_values", "gather_host_blobs", "all_hosts_ok",
    "coordinated_any",
    "commit_after_all_hosts", "broadcast_one_to_all",
    "verify_across_processes", "process_allgather",
    "sync_global_devices", "_vote", "_coordinated_recover",
}

#: Nondeterministic-at-trace-time call prefixes (resolved roots).
NONDET_PREFIXES = (
    "time.", "numpy.random.", "random.", "datetime.", "uuid.",
    "secrets.", "os.urandom", "os.getpid", "os.times",
)

#: Per-host-divergent condition sources for the collective rule.
DIVERGENT_PREFIXES = (
    "os.", "time.", "glob.", "random.", "numpy.random.", "socket.",
    "shutil.", "tempfile.", "pathlib.",
)
DIVERGENT_ATTRS = {"process_index", "exists", "isfile", "isdir",
                   "listdir", "errno", "pid", "getmtime", "stat"}
DIVERGENT_BUILTINS = {"open", "input"}

#: Host-sync call spellings.
SYNC_FUNCS = {"float", "int", "bool", "complex"}
SYNC_DOTTED = {"numpy.asarray", "numpy.array", "jax.device_get"}
SYNC_METHODS = {"item", "tolist", "block_until_ready"}

#: tpudp.obs recorder API split (the obs-in-hot-path rule): the
#: ALLOCATION-FREE calls sanctioned on designated hot paths vs the
#: convenient allocating forms that belong off them.
OBS_FAST_METHODS = {"begin", "end", "count"}
OBS_ALLOC_METHODS = {"span", "event"}


def _hot_functions(mod: Module):
    """Defs designated as scheduler/step hot paths in this module —
    via the repo table or a ``# tpudp: hot-path`` marker on/above the
    def line."""
    table = set()
    for path, quals in HOT_PATHS.items():
        if mod.rel.endswith(path):
            table = quals
            break
    for fn, qual in mod.functions.items():
        if qual in table:
            yield fn
            continue
        start = fn.lineno
        if fn.decorator_list:
            start = fn.decorator_list[0].lineno
        if any("tpudp: hot-path" in mod.comments.get(line, "")
               for line in range(max(1, start - 1), fn.lineno + 1)):
            yield fn


def _in_scope(mod: Module, prefixes, marker: str) -> bool:
    if marker in mod.markers:
        return True
    return any(mod.rel.endswith(p) if p.endswith(".py")
               else p in mod.rel for p in prefixes)


def _assign_targets(node):
    """Raw dotted target paths of an Assign/AugAssign/For/With."""
    targets = []
    if isinstance(node, ast.Assign):
        targets = node.targets
    elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
        targets = [node.target]
    flat = []
    for t in targets:
        if isinstance(t, (ast.Tuple, ast.List)):
            flat.extend(t.elts)
        else:
            flat.append(t)
    return flat


class TraceNondeterminism(Rule):
    """Wall clocks, host RNGs, and process identity inside traced code.

    A value drawn from ``time.*``/``np.random``/``random`` during
    tracing is frozen into the jaxpr as a constant: the program is no
    longer a function of its inputs, replays differently across
    processes (host-divergent constants feed host-divergent collectives
    on a pod), and defeats bit-exact trajectory replay.  Use
    ``jax.random`` with explicit keys, or compute the value on the host
    and pass it as an argument.
    """

    name = "trace-nondeterminism"
    summary = ("host clock/RNG/process-identity call inside traced code "
               "— becomes a trace-time constant")

    def check(self, mod: Module):
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            if mod.traced_kind(node) is None:
                continue
            dotted = mod.dotted(node.func)
            if dotted is None:
                continue
            if dotted.startswith("jax."):
                continue
            if any(dotted == p.rstrip(".") or dotted.startswith(p)
                   for p in NONDET_PREFIXES):
                yield self.finding(
                    mod, node,
                    f"{dotted}() inside traced code freezes a "
                    f"host-nondeterministic value into the jaxpr; pass it "
                    f"in as an argument or use jax.random")


class UnorderedIteration(Rule):
    """Unordered iteration feeding trace constants or host protocols.

    Iterating a ``set`` during tracing bakes an interpreter-dependent
    order into the program (PYTHONHASHSEED changes it run to run), so
    two hosts can trace different programs from identical sources —
    the exact recompile/collective-mismatch class PR 7's vote protocol
    exists to survive.  In coordination modules the same applies to
    unsorted ``os.listdir`` results feeding checkpoint walks.
    """

    name = "unordered-iteration"
    summary = ("iteration order is interpreter-dependent (set iteration "
               "in traced code / unsorted os.listdir in a coordination "
               "module)")

    def _is_set_expr(self, mod, node):
        if isinstance(node, (ast.Set, ast.SetComp)):
            return True
        if isinstance(node, ast.Call):
            return mod.dotted(node.func) in ("set", "frozenset")
        return False

    def check(self, mod: Module):
        for node in ast.walk(mod.tree):
            iters = []
            if isinstance(node, (ast.For, ast.AsyncFor)):
                iters = [node.iter]
            elif isinstance(node, (ast.ListComp, ast.SetComp,
                                   ast.GeneratorExp, ast.DictComp)):
                iters = [g.iter for g in node.generators]
            for it in iters:
                if (self._is_set_expr(mod, it)
                        and mod.traced_kind(node) is not None):
                    yield self.finding(
                        mod, it,
                        "set iteration order is interpreter-dependent; "
                        "inside traced code it bakes a per-process order "
                        "into the program — sort it first")
        if _in_scope(mod, COLLECTIVE_MODULE_PREFIXES, "collective-module"):
            for node in ast.walk(mod.tree):
                if (isinstance(node, ast.Call)
                        and mod.dotted(node.func) == "os.listdir"):
                    # any enclosing sorted() within the statement
                    # normalizes the order (incl. comprehensions fed to
                    # sorted)
                    cur, in_sorted = mod.parents.get(node), False
                    while cur is not None and not isinstance(cur, ast.stmt):
                        if (isinstance(cur, ast.Call)
                                and mod.dotted(cur.func) == "sorted"):
                            in_sorted = True
                            break
                        cur = mod.parents.get(cur)
                    if in_sorted:
                        continue
                    yield self.finding(
                        mod, node,
                        "os.listdir order is filesystem-dependent; in a "
                        "cross-host coordination module wrap it in "
                        "sorted() so every host walks the same order")


class TracedBranch(Rule):
    """Python ``if``/``while`` on a traced value.

    Inside a jitted function, ``if x > 0:`` forces ``x`` concrete at
    trace time: either it raises ``ConcretizationTypeError``, or — when
    the branch input happens to be weakly typed — it silently
    specializes the program to one branch and recompiles when the value
    flips shape-class.  Branch with ``lax.cond``/``jnp.where``, or mark
    the argument static.
    """

    name = "traced-branch"
    summary = ("Python control flow on a traced value — trace error or "
               "silent per-value specialization/recompile")

    def check(self, mod: Module):
        for fn in mod.functions:
            params = mod.traced_params(fn)
            if not params:
                continue
            tainted = set(params)
            for node in ordered_walk(fn):
                if isinstance(node, ast.Assign):
                    hit = mentions(mod, node.value, tainted)
                    for t in _assign_targets(node):
                        dotted = mod.raw_dotted(t)
                        if dotted is None:
                            continue
                        if hit:
                            tainted.add(dotted)
                        else:
                            tainted.discard(dotted)
                elif isinstance(node, (ast.If, ast.While)):
                    if mentions(mod, node.test, tainted):
                        kind = ("while" if isinstance(node, ast.While)
                                else "if")
                        yield self.finding(
                            mod, node,
                            f"Python `{kind}` on a traced value in "
                            f"jitted `{fn.name}` — use lax.cond/"
                            f"jnp.where or a static argument")


class HostSync(Rule):
    """Device→host synchronization where it stalls the pipeline.

    Two scopes.  (1) Traced code: ``float()``/``np.asarray()``/
    ``.item()`` on a traced value fails at trace time — flagged here so
    review catches it before the first trace.  (2) Designated
    scheduler/step hot paths: each sync is a full round trip per call
    under async dispatch; every *intentional* one (the window-edge
    loss fetch, the per-token commit) must carry a visible
    ``lint-ok(host-sync)`` so new ones can't slip in as a diff nobody
    notices (the on-device decode loop exists to delete the suppressed
    ones).
    """

    name = "host-sync"
    summary = ("device→host sync (.item()/float()/np.asarray/"
               "device_get) in traced code or a scheduler hot path")

    def _sync_call(self, mod, node, tainted):
        """(description, node) when ``node`` is a sync op on a tainted
        value."""
        if not isinstance(node, ast.Call):
            return None
        dotted = mod.dotted(node.func)
        if dotted in SYNC_DOTTED and node.args:
            if dotted == "jax.device_get" or mentions(
                    mod, node.args[0], tainted):
                return dotted
        if (isinstance(node.func, ast.Name)
                and node.func.id in SYNC_FUNCS and node.args
                and mentions(mod, node.args[0], tainted)):
            return f"{node.func.id}()"
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr in SYNC_METHODS
                and mentions(mod, node.func.value, tainted)):
            return f".{node.func.attr}()"
        return None

    def _scan(self, mod, fn, tainted, where):
        reported: set[int] = set()

        def assign_one(target, value):
            dotted = mod.raw_dotted(target)
            # flag every sync nested ANYWHERE in the value with the
            # PRE-assignment taint — `x = max(float(x), 1.0)` must fire
            # even though the assignment itself untaints `x` (the later
            # generic Call visit would see the already-cleared taint)
            desc = None
            for sub in ast.walk(value):
                if not isinstance(sub, ast.Call) or id(sub) in reported:
                    continue
                sub_desc = self._sync_call(mod, sub, tainted)
                if sub_desc is not None:
                    reported.add(id(sub))
                    out.append(self.finding(
                        mod, sub,
                        f"{sub_desc} forces a device→host sync {where}"))
                    if sub is value:
                        desc = sub_desc
            if dotted is None:
                return
            if desc is not None:
                # the sync itself was flagged; its result is a host
                # value — don't re-flag downstream reads
                tainted.discard(dotted)
            elif (mentions(mod, value, tainted)
                    or self._device_value(mod, value)):
                tainted.add(dotted)
            else:
                tainted.discard(dotted)

        out: list = []
        for node in ordered_walk(fn):
            if isinstance(node, ast.Assign):
                targets = _assign_targets(node)
                # pairwise tuple semantics: `a, b = float(x), y` syncs
                # into `a` only
                if (len(node.targets) == 1
                        and isinstance(node.targets[0], (ast.Tuple, ast.List))
                        and isinstance(node.value, (ast.Tuple, ast.List))
                        and len(targets) == len(node.value.elts)):
                    for t, v in zip(targets, node.value.elts):
                        assign_one(t, v)
                else:
                    for t in targets:
                        assign_one(t, node.value)
            elif isinstance(node, ast.Call) and id(node) not in reported:
                desc = self._sync_call(mod, node, tainted)
                if desc is not None:
                    reported.add(id(node))
                    out.append(self.finding(
                        mod, node,
                        f"{desc} forces a device→host sync {where}"))
        yield from out

    def _device_value(self, mod, node) -> bool:
        """Calls that mint device values (hot-path taint seeds)."""
        if not isinstance(node, ast.Call):
            return any(self._device_value(mod, c)
                       for c in ast.iter_child_nodes(node))
        dotted = mod.dotted(node.func)
        if dotted and (dotted.startswith("jax.numpy.")
                       or dotted.startswith("jax.random.")):
            return True
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr in DEVICE_CALL_ATTRS):
            return True
        if (isinstance(node.func, ast.Name)
                and node.func.id in DEVICE_CALL_ATTRS):
            return True
        return False

    def check(self, mod: Module):
        hot = set(_hot_functions(mod))
        for fn in mod.functions:
            if fn in hot:
                roots = set()
                for path, seeds in DEVICE_ROOTS.items():
                    if mod.rel.endswith(path):
                        roots = set(seeds)
                yield from self._scan(
                    mod, fn, roots,
                    f"on the `{fn.name}` hot path — one round trip per "
                    f"call under async dispatch")
            else:
                params = mod.traced_params(fn)
                if params:
                    yield from self._scan(
                        mod, fn, set(params),
                        f"inside traced `{fn.name}` — this fails at "
                        f"trace time")


class UseAfterDonation(Rule):
    """Reading a buffer after passing it to a donating program.

    ``donate_argnums`` hands the buffer to XLA to overwrite in place;
    the Python reference left behind points at deleted memory, and
    touching it raises ``RuntimeError: Array has been deleted`` — but
    only on backends that actually alias (TPU), so CPU tests pass while
    the pod run crashes.  Rebind the result before the next read, and
    refresh the variable inside loops.
    """

    name = "use-after-donation"
    summary = ("buffer read after being donated to a jitted program "
               "(donate_argnums) — deleted on aliasing backends")

    def _donating_targets(self, mod: Module):
        """name → donated indices for defs in this module with
        donate_argnums decorators."""
        local = {}
        for fn in mod.functions:
            _, _, donated = mod._jit_decorator_info(fn)
            if donated:
                local[fn.name] = donated
        return local

    @staticmethod
    def _donated(ref, local):
        """(name, donated indices) of a callable reference.  A bare name
        may be one of this module's own jit defs (``local``); an
        attribute (``ms.decode_step``) never is — it may be the same def
        bound to leading arguments, so only the call-site table counts."""
        if isinstance(ref, ast.Attribute):
            return ref.attr, DONATING.get(ref.attr)
        if isinstance(ref, ast.Name):
            return ref.id, local.get(ref.id, DONATING.get(ref.id))
        return None, None

    def _call_donations(self, mod, node, local):
        """Yield (donated_arg_expr, label) for a donating call."""
        name, donated = self._donated(node.func, local)
        if name in DEVICE_WRAPPERS and len(node.args) >= 2:
            fn_pos, arg_start = DEVICE_WRAPPERS[name]
            iname, donated = self._donated(node.args[fn_pos], local)
            for idx in donated or ():
                pos = arg_start + idx
                if pos < len(node.args):
                    yield node.args[pos], iname
            return
        for idx in donated or ():
            if idx < len(node.args):
                yield node.args[idx], name

    def check(self, mod: Module):
        local = self._donating_targets(mod)
        for fn in mod.functions:
            if mod.traced_kind(fn) in ("root", "combinator", "nested"):
                continue  # inside a trace, "donation" is the caller's jit
            # positions of loads/stores of every dotted path in fn
            events = []  # (line, col, kind, dotted)
            for node in ast.walk(fn):
                dotted = mod.raw_dotted(node)
                if dotted is None or not isinstance(
                        node, (ast.Name, ast.Attribute)):
                    continue
                parent = mod.parents.get(node)
                if isinstance(parent, ast.Attribute):
                    continue  # only record the full chain once
                kind = ("store" if isinstance(
                    node.ctx, (ast.Store, ast.Del)) else "load")
                events.append((node.lineno, node.col_offset, kind,
                               dotted, node))
            events.sort(key=lambda e: (e[0], e[1]))

            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                for arg, label in self._call_donations(mod, node, local):
                    path = mod.raw_dotted(arg)
                    if path is None:
                        continue
                    stmt = node
                    while (mod.parents.get(stmt) is not None
                           and not isinstance(stmt, ast.stmt)):
                        stmt = mod.parents[stmt]
                    # same-statement rebind (x = f(x)) is the idiom
                    rebound = isinstance(stmt, ast.Assign) and any(
                        mod.raw_dotted(t) == path
                        or (mod.raw_dotted(t) is not None
                            and path.startswith(mod.raw_dotted(t) + "."))
                        for t in _assign_targets(stmt))
                    end = getattr(stmt, "end_lineno", stmt.lineno)
                    if not rebound:
                        for line, col, kind, dotted, enode in events:
                            if line <= end:
                                continue
                            related = (dotted == path
                                       or dotted.startswith(path + ".")
                                       or path.startswith(dotted + "."))
                            if not related:
                                continue
                            if kind == "store":
                                break
                            yield self.finding(
                                mod, enode,
                                f"`{dotted}` read after being donated to "
                                f"`{label}` at line {node.lineno} — "
                                f"deleted on aliasing backends; rebind "
                                f"the program's result first")
                            break
                    # loop-carried donation: the next iteration passes a
                    # deleted buffer unless the path is rebound in-loop
                    cur = mod.parents.get(node)
                    loop = None
                    while cur is not None and cur is not fn:
                        if isinstance(cur, (ast.For, ast.While)):
                            loop = cur
                            break
                        cur = mod.parents.get(cur)
                    if loop is not None:
                        stored = any(
                            e[2] == "store" and (
                                e[3] == path
                                or path.startswith(e[3] + "."))
                            for e in events
                            if loop.lineno <= e[0]
                            <= getattr(loop, "end_lineno", loop.lineno))
                        if not stored:
                            yield self.finding(
                                mod, node,
                                f"`{path}` is donated to `{label}` inside "
                                f"a loop but never rebound in the loop "
                                f"body — the second iteration passes a "
                                f"deleted buffer")


class DivergentCollective(Rule):
    """Collectives issued under per-host-divergent control flow.

    A collective is a rendezvous: every participating host must issue
    the same sequence.  One guarded by ``if jax.process_index() == 0``,
    an ``except`` handler, or a filesystem/clock condition can be
    entered by some hosts and skipped by others — on a pod that is a
    deadlock (multi-minute stall, then a watchdog kill), not an
    exception.  Route per-host outcomes through the vote protocol
    (every host reaches the gather; the *decision* is collective) and
    suppress here with the justification.
    """

    name = "divergent-collective"
    summary = ("collective/cross-process call under per-host-divergent "
               "control flow (except handler, process_index/filesystem/"
               "clock condition) — pod deadlock")

    def _divergent_expr(self, mod, node, tainted) -> bool:
        if isinstance(node, ast.Call):
            if self._is_collective(mod, node):
                # the RESULT of a vote/collective is host-uniform by
                # construction — branching on it is the sanctioned
                # pattern, whatever per-host facts fed the vote
                return False
            dotted = mod.dotted(node.func)
            if dotted:
                if any(dotted.startswith(p) for p in DIVERGENT_PREFIXES):
                    return True
                if dotted in DIVERGENT_BUILTINS:
                    return True
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr in DIVERGENT_ATTRS):
                return True
        elif isinstance(node, (ast.Name, ast.Attribute)):
            dotted = mod.raw_dotted(node)
            if dotted and dotted in tainted:
                return True
            if (isinstance(node, ast.Attribute)
                    and node.attr in DIVERGENT_ATTRS):
                return True
        return any(self._divergent_expr(mod, c, tainted)
                   for c in ast.iter_child_nodes(node))

    def _is_collective(self, mod, node) -> bool:
        dotted = mod.dotted(node.func)
        if dotted in COLLECTIVE_CALLS:
            return True
        if dotted and dotted.startswith("jax.experimental.multihost_utils."):
            return True
        name = None
        if isinstance(node.func, ast.Attribute):
            name = node.func.attr
        elif isinstance(node.func, ast.Name):
            name = node.func.id
        return name in COLLECTIVE_HELPERS

    def _under_divergent_control(self, mod, node, fn, tainted) -> bool:
        """Is this statement lexically inside an except handler or a
        branch gated on a divergent condition?  An assignment there is
        control-dependent on per-host state even when its RHS is a
        constant (`flag = True` under `if os.path.exists(...)`)."""
        cur = mod.parents.get(node)
        while cur is not None and cur is not fn:
            if isinstance(cur, ast.ExceptHandler):
                return True
            if isinstance(cur, (ast.If, ast.While)) and \
                    self._divergent_expr(mod, cur.test, tainted):
                return True
            cur = mod.parents.get(cur)
        return False

    def check(self, mod: Module):
        # taint pass per function: names assigned from divergent
        # sources — by DATA flow (divergent RHS) or by CONTROL flow
        # (any assignment under a divergent branch).  Iterated to a
        # fixpoint so `a = os.*; if a: b = True; if b: collective()`
        # chains resolve.
        for fn in mod.functions:
            tainted: set[str] = set()
            changed = True
            while changed:
                changed = False
                for node in ast.walk(fn):
                    if not isinstance(node, ast.Assign):
                        continue
                    if (self._divergent_expr(mod, node.value, tainted)
                            or self._under_divergent_control(
                                mod, node, fn, tainted)):
                        for t in _assign_targets(node):
                            dotted = mod.raw_dotted(t)
                            if dotted is not None and dotted not in tainted:
                                tainted.add(dotted)
                                changed = True
            for node in ast.walk(fn):
                if not (isinstance(node, ast.Call)
                        and self._is_collective(mod, node)):
                    continue
                cur = mod.parents.get(node)
                prev = node
                while cur is not None and cur is not fn:
                    if isinstance(cur, ast.ExceptHandler):
                        yield self.finding(
                            mod, node,
                            "collective issued inside an except handler — "
                            "exception occurrence is per-host, so peers "
                            "that didn't fault never reach the rendezvous "
                            "(vote at an unconditional decision point "
                            "instead)")
                        break
                    if isinstance(cur, (ast.If, ast.While)):
                        # the `test` itself runs unconditionally
                        in_test = any(prev is c or prev in ast.walk(c)
                                      for c in [cur.test])
                        if not in_test and self._divergent_expr(
                                mod, cur.test, tainted):
                            yield self.finding(
                                mod, node,
                                "collective under a per-host-divergent "
                                "condition — hosts taking different "
                                "branches deadlock the rendezvous")
                            break
                    prev = cur
                    cur = mod.parents.get(cur)


def _bumps_trace_counts(fn) -> bool:
    """Does this def's body contain ``TRACE_COUNTS[...] += 1``?"""
    for node in ast.walk(fn):
        if (isinstance(node, ast.AugAssign)
                and isinstance(node.target, ast.Subscript)
                and isinstance(node.target.value, ast.Name)
                and node.target.value.id == "TRACE_COUNTS"):
            return True
    return False


class UnregisteredJit(Rule):
    """Jitted programs in compile-once modules must be observable.

    The serve layer's compile-once invariant is enforced by tests that
    watch ``TRACE_COUNTS``; a jitted program that doesn't bump a
    counter is invisible to them, so a recompile regression in it
    ships silently.  Every jit in scope bumps
    ``TRACE_COUNTS[<name>]`` as the first traced side effect and is
    then eligible for the trace-stability audit registry
    (tpudp/analysis/programs.py).
    """

    name = "unregistered-jit"
    summary = ("jitted program in a compile-once module does not bump "
               "TRACE_COUNTS — recompiles in it are unobservable")

    def _bumps_trace_counts(self, fn) -> bool:
        return _bumps_trace_counts(fn)

    def check(self, mod: Module):
        if not _in_scope(mod, COMPILE_ONCE_PREFIXES, "compile-once-module"):
            return
        for fn in mod.functions:
            rooted, _, _ = mod._jit_decorator_info(fn)
            if rooted and not self._bumps_trace_counts(fn):
                yield self.finding(
                    mod, fn,
                    f"jitted `{fn.name}` never bumps TRACE_COUNTS — its "
                    f"recompiles are invisible to the compile-once tests; "
                    f"add TRACE_COUNTS[\"{fn.name}\"] += 1 in the traced "
                    f"body and register it for the trace audit")
        # call-form jits too: `fast = jax.jit(body)` / partial(jax.jit)
        # — same invisibility, different spelling
        by_name: dict[str, list] = {}
        for fn in mod.functions:
            by_name.setdefault(fn.name, []).append(fn)
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Call)):
                continue
            call = node.value
            dotted = mod.dotted(call.func)
            inner = None
            if dotted in ("jax.jit", "jax.pjit") and call.args:
                inner = call.args[0]
            elif (dotted in ("functools.partial", "partial") and call.args
                    and mod.dotted(call.args[0]) in ("jax.jit", "jax.pjit")
                    and len(call.args) > 1):
                inner = call.args[1]
            if not isinstance(inner, ast.Name):
                continue
            for fn in by_name.get(inner.id, ()):
                if not self._bumps_trace_counts(fn):
                    yield self.finding(
                        mod, node,
                        f"call-form jit of `{fn.name}` never bumps "
                        f"TRACE_COUNTS — its recompiles are invisible to "
                        f"the compile-once tests; add "
                        f"TRACE_COUNTS[\"{fn.name}\"] += 1 in the traced "
                        f"body and register it for the trace audit")


class UnregisteredKernel(Rule):
    """Pallas kernels outside the pinned program registry.

    Every hand-written kernel on the serving hot path is pinned in the
    trace-audit registry (tpudp/analysis/programs.py) through the
    program that dispatches it: the program bumps its TRACE_COUNTS key,
    the key maps to a registered program name, and the lockfile carries
    the kernel body's fingerprint.  A ``pl.pallas_call`` reachable from
    code that is neither inside a counter-bumping program nor inside a
    wrapper marked ``# tpudp: kernel-program(<registered name>)`` is a
    kernel whose body can change without any named, reviewed lockfile
    event — exactly the silent-regression class the audit exists to
    close (mirrors ``unregistered-jit``, one layer down).
    """

    name = "unregistered-kernel"
    summary = ("pl.pallas_call site not tied to a registered trace-audit "
               "program — kernel-body changes would dodge the lock")

    def _program_marker(self, mod: Module, fn) -> str | None:
        """``# tpudp: kernel-program(NAME)`` on the def line or the
        line above it (the hot-path marker placement)."""
        start = fn.lineno
        if fn.decorator_list:
            start = fn.decorator_list[0].lineno
        for line in range(max(1, start - 1), fn.lineno + 1):
            m = KERNEL_PROGRAM_RE.search(mod.comments.get(line, ""))
            if m:
                return m.group(1)
        return None

    def check(self, mod: Module):
        if not _in_scope(mod, KERNEL_SCOPE_PREFIXES, "kernel-module"):
            return
        # Stdlib-safe: programs.py's module level is pure tables (the
        # heavy imports live inside its builders).
        from .programs import TRACE_COUNTER_PROGRAMS
        registered = set(TRACE_COUNTER_PROGRAMS.values())
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            dotted = mod.dotted(node.func) or ""
            if dotted.split(".")[-1] != "pallas_call":
                continue
            marker, covered = None, False
            fn = mod.enclosing_function(node)
            while fn is not None:
                if _bumps_trace_counts(fn):
                    covered = True  # inside a counted (hence registered
                    break           # or registry-test-caught) program
                if marker is None:
                    marker = self._program_marker(mod, fn)
                fn = mod.enclosing_function(fn)
            if covered or marker in registered:
                continue
            if marker is None:
                yield self.finding(
                    mod, node,
                    "pl.pallas_call site belongs to no registered "
                    "program — dispatch it from a TRACE_COUNTS-bumping "
                    "program, or mark its wrapper `# tpudp: "
                    "kernel-program(<name>)` with a name from "
                    "TRACE_COUNTER_PROGRAMS")
            else:
                yield self.finding(
                    mod, node,
                    f"kernel-program({marker}) names no registered "
                    f"program — register it in tpudp/analysis/"
                    f"programs.py (TRACE_COUNTER_PROGRAMS + "
                    f"build_programs) so the kernel body is pinned")


class ObsInHotPath(Rule):
    """Allocating telemetry calls on designated scheduler hot paths.

    Instrumentation must pass the same bar as the code it observes:
    ``tpudp.obs``'s ``span(...)``/``event(...)`` build dicts and context
    managers per call — fine at request admission or a recovery
    decision, a per-token allocation regression inside
    ``Engine.step``/``_run_decode``/``Trainer.train_epoch``.  The
    recorder's allocation-free ``begin``/``end``/``count`` API exists
    precisely for those paths (tpudp/obs/record.py documents the
    contract), so on a hot path ONLY that API is allowed — the same
    "every exception is visible in the diff" discipline as the
    host-sync rule's suppressions.
    """

    name = "obs-in-hot-path"
    summary = ("allocating obs recorder call (.span()/.event()) on a "
               "designated hot path — use the allocation-free "
               "begin()/end()/count() API")

    def check(self, mod: Module):
        for fn in _hot_functions(mod):
            for node in ast.walk(fn):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr in OBS_ALLOC_METHODS):
                    continue
                yield self.finding(
                    mod, node,
                    f".{node.func.attr}() allocates per call on the "
                    f"`{fn.name}` hot path — record through the "
                    f"allocation-free begin()/end()/count() API (or move "
                    f"the event off the hot path)")


RULES = [
    TraceNondeterminism(),
    UnorderedIteration(),
    TracedBranch(),
    HostSync(),
    UseAfterDonation(),
    DivergentCollective(),
    UnregisteredJit(),
    UnregisteredKernel(),
    ObsInHotPath(),
]

RULES_BY_NAME = {r.name: r for r in RULES}
