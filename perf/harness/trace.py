"""From the profiler's trace to device busy/idle, operations and gaps.

Two steps, so that the arithmetic can be checked on a small recorded
trace without a chip:

  extract(xplane file) -> a plain dict: per device the operations
      ``[label, start_s, duration_s, is_kernel, opcode]`` of its "XLA Ops"
      line, and the harness's own spans (``perf.*`` TraceAnnotations)
      from the host plane, all on the trace's one clock;
  reduce(dict) -> busy seconds, the window, time per operation name,
      idle gaps by what the host was doing, collective time not hidden
      behind compute, and the share spent in Mosaic kernels.

What a TPU v5e trace looks like (looked at by hand, PR 24): one plane per
chip named ``/device:TPU:<n>`` whose lines include "XLA Modules" (one
event per executed program), "XLA Ops" (one event per HLO operation,
named by the instruction's whole text, in program order on the one
TensorCore, nested for ``while``/``conditional`` bodies), "Async XLA Ops" (start to done of
asynchronous copies and collectives) and "Steps"; the host is
``/host:CPU`` with one line per thread, the harness's annotations on
``main``.
"""

from __future__ import annotations

import glob
import os
import re

from perf.harness import stats

WINDOW_SPAN = "perf.traced_window"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
COLLECTIVE = re.compile(  # matched against an opcode, never a whole text
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast)")
CONTAINERS = ("while", "conditional", "call")  # their time is their body's


def profiler_options():
    """Host TraceMe annotations on, the Python tracer off: the harness's
    spans are all the host detail the reduction reads, and per-call
    Python events would slow the loop under test."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def newest_xplane(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return files[-1] if files else None


_NAME = re.compile(r"^%(\S+) = ")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_KIND = re.compile(r"kind=(k\w+)")


def parse_op(text: str) -> tuple[str, str, bool]:
    """``(label, opcode, is_kernel)`` of one "XLA Ops" event, whose name is
    the HLO instruction's whole text: ``%name = shape opcode(operands),
    attributes``.  The label keeps the instruction's name, opcode, fusion
    kind and output shape (layouts dropped); a Mosaic (Pallas) kernel is a
    ``custom-call`` whose target is ``tpu_custom_call``."""
    name = _NAME.match(text)
    if name is None:
        return text[:96], "", False
    rest = _LAYOUT.sub("", text[name.end():])
    op = _OPCODE.search(" " + rest)
    if op is None:
        return text[:96], "", False
    kind = _KIND.search(text)
    shape = rest[:max(op.start() - 1, 0)]
    label = f"{name[1]} {op[1]}{':' + kind[1] if kind else ''} {shape}"
    return label[:96], op[1], (
        op[1] == "custom-call"
        and 'custom_call_target="tpu_custom_call"' in text)


def extract(xplane_path: str) -> dict:
    """The plain form of a trace: what ``reduce`` reads, and nothing of
    the file's format.  ``devices``: per chip ``[label, start_s, dur_s,
    is_kernel, opcode]`` of the "XLA Ops" line; ``async``: the same for
    the collectives of the "Async XLA Ops" line (start to done, hidden
    part included); ``host``: the harness's ``perf.*`` annotations."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    devices: dict[str, list] = {}
    asyncs: dict[str, list] = {}
    host: list = []
    parsed: dict[str, tuple] = {}  # every step repeats the same texts
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name not in (OPS_LINE, ASYNC_LINE):
                    continue
                for ev in line.events:
                    if ev.name not in parsed:
                        parsed[ev.name] = parse_op(ev.name)
                    label, op, kernel = parsed[ev.name]
                    row = [label, ev.start_ns * 1e-9, ev.duration_ns * 1e-9,
                           kernel, op]
                    if line.name == OPS_LINE:
                        devices.setdefault(plane.name, []).append(row)
                    elif COLLECTIVE.match(op):
                        asyncs.setdefault(plane.name, []).append(row)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("perf."):
                        host.append([ev.name, ev.start_ns * 1e-9,
                                     ev.duration_ns * 1e-9])
    return {"devices": devices, "async": asyncs, "host": host}


def _self_times(ops):
    """``(name, self seconds, is_kernel)`` per event: an operation's time
    less that of the operations nested in it (a ``while`` holds its
    body's operations)."""
    out = []
    stack: list[list] = []  # [end, index into out]
    for name, start, dur, kernel, _op in sorted(ops,
                                                key=lambda o: (o[1], -o[2])):
        end = start + dur
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            parent = out[stack[-1][1]]
            parent[1] -= min(dur, max(stack[-1][0] - start, 0.0))
        out.append([name, dur, kernel])
        stack.append([end, len(out) - 1])
    return out


def _attribute(gaps, spans):
    """Seconds of ``gaps`` by the innermost harness span that covers
    them; what no span covers is ``outside_spans``."""
    by_name: dict[str, float] = {}
    # innermost first: a later start and shorter span nests inside
    order = sorted(spans, key=lambda s: (-s[1], s[2]))
    for g0, g1 in gaps:
        left = [(g0, g1)]
        for name, s0, dur in order:
            if not left:
                break
            s1 = s0 + dur
            if s1 <= g0 or s0 >= g1:
                continue
            covered = stats.clip(left, s0, s1)
            if covered:
                by_name[name] = by_name.get(name, 0.0) + stats.total(covered)
                left = stats.subtract(left, covered)
        rest = stats.total(left)
        if rest > 0:
            by_name["outside_spans"] = by_name.get("outside_spans", 0.0) + rest
    return by_name


def reduce(form: dict) -> dict | None:
    """Metrics of one traced window; None where no device operation ran
    in it (a reader then returns nothing)."""
    windows = [h for h in form["host"] if h[0] == WINDOW_SPAN]
    if not windows or not form["devices"]:
        return None
    w0 = windows[-1][1]
    w1 = w0 + windows[-1][2]
    spans = [h for h in form["host"]
             if h[0] != WINDOW_SPAN and h[1] < w1 and h[1] + h[2] > w0]
    n = len(form["devices"])
    busy = exposed = collective = kernel = 0.0
    op_time: dict[str, float] = {}
    gap_time: dict[str, float] = {}
    for plane, ops in form["devices"].items():
        inside = [o for o in ops if o[1] < w1 and o[1] + o[2] > w0]
        hidden = [o for o in form.get("async", {}).get(plane, [])
                  if o[1] < w1 and o[1] + o[2] > w0]
        ivs = stats.clip(stats.union((o[1], o[1] + o[2]) for o in inside),
                         w0, w1)
        busy += stats.total(ivs)
        coll = stats.clip(stats.union(
            (o[1], o[1] + o[2]) for o in inside if COLLECTIVE.match(o[4])),
            w0, w1)
        compute = stats.clip(stats.union(
            (o[1], o[1] + o[2]) for o in inside
            if not COLLECTIVE.match(o[4]) and o[4] not in CONTAINERS),
            w0, w1)
        collective += stats.total(stats.clip(stats.union(
            [(o[1], o[1] + o[2]) for o in hidden] + coll), w0, w1))
        exposed += stats.total(stats.subtract(coll, compute))
        for name, self_s, is_k in _self_times(inside):
            op_time[name] = op_time.get(name, 0.0) + self_s
            if is_k:
                kernel += self_s
        gaps = stats.subtract([(w0, w1)], ivs)
        for name, s in _attribute(gaps, spans).items():
            gap_time[name] = gap_time.get(name, 0.0) + s
    if busy <= 0:
        return None
    top = lambda d: [[k, v / n] for k, v in  # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"window_s": w1 - w0, "busy_s": busy / n, "devices": n,
            "collective_s": collective / n,
            "collective_exposed_s": exposed / n,
            "kernel_s": kernel / n,
            "device_ops": top(op_time), "idle_gaps": top(gap_time)}
