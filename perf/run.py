"""The benchmark's one command.

    python3 perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on: set-up
(weights on the device from the seed, compile or cache retrieval, warm-up of
the cell's own shapes, the correctness check), then a measured window of
``--seconds`` in which nothing compiles, then one JSON object as the last
line of standard output.  Every number ``correct`` compared stands beside
its limit in the last lines of standard error and under the line's last
key, ``compared``.  ``--trace 0`` reports the cell's end-to-end
metrics; ``--trace 1`` measures the same window with spans on, then a few
more seconds under the profiler, and reports the cell's per-layer metrics,
the device's busy seconds and a breakdown.

Without a TPU (or with fewer chips than the cell asks for) it exits
non-zero and prints no result.  ``--rehearse`` (never passed by the driver)
runs the files' tiny ``rehearsal`` presets on the CPU to exercise the
control flow; its line says ``platform=cpu`` and is not a measurement.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_SECONDS = 3.0
# a directory a process: two traced runs of one checkout at once (tests
# under several workers) would otherwise remove each other's trace
TRACE_DIR = os.path.join(ROOT, "bench_results", "perf_trace",
                         str(os.getpid()))
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Run:
    """What a driver and the per-layer readers see of one run."""

    def __init__(self, cell, seed: int, devices, rehearse: bool,
                 layers: bool):
        from perf.harness.spans import Spans

        self.cell = cell
        self.seed = seed
        self.devices = devices
        self.rehearse = rehearse
        self.layers = layers  # a --trace 1 run: per-layer readings wanted
        self.device_kind = devices[0].device_kind
        self.spans = Spans()
        self.compiles = 0
        self.window: dict = {}         # the measured window's segment
        self.traced: dict | None = None  # the profiled segment
        self.trace_form: dict | None = None  # its trace in the plain form
        self.trace: dict | None = None       # and the reduction of that
        self.end_to_end: dict = {}
        self.window_compiles = 0


def fail(msg: str, code: int = 2):
    print(f"[perf] {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny presets on the CPU; labels platform=cpu")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    from perf.harness.cells import Cell, CellError

    try:
        cell = Cell(args.workload, root=ROOT, rehearse=args.rehearse)
    except (CellError, OSError, KeyError, ValueError) as e:
        fail(f"cannot load cell {args.workload!r}: {e!r}")
    seconds = args.seconds if args.seconds is not None \
        else float(cell.bench["run_seconds"])

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell.chips}")
    os.environ.setdefault("TPUDP_NO_DOWNLOAD", "1")  # sealed machine
    try:
        import jax

        import tpudp  # noqa: F401 — the system under test
    except ImportError as e:
        fail(f"the system under test is not importable here: {e!r}")
    try:
        devices = jax.devices()
    except RuntimeError as e:
        fail(f"JAX found no device: {e!r}")
    want = "cpu" if args.rehearse else "tpu"
    if devices[0].platform != want or len(devices) < cell.chips:
        fail(f"cell {cell.name} needs {cell.chips} {want} device(s); JAX "
             f"found {len(devices)} x {devices[0].platform}")
    if not args.rehearse:
        from perf.harness.peaks import peaks
        from tpudp.utils.compile_cache import enable_persistent_cache

        peaks(devices[0].device_kind)  # unknown kind: an error, now
        # JAX_COMPILATION_CACHE_DIR if set, else a fixed path in the checkout
        enable_persistent_cache()

    run = Run(cell, args.seed, devices[:cell.chips], args.rehearse,
              bool(args.trace))

    def on_compile(event, _secs, **_kw):
        if event == COMPILE_EVENT:
            run.compiles += 1

    jax.monitoring.register_event_duration_secs_listener(on_compile)

    run.spans.rows.append(("perf.setup.imports_and_device", T_START,
                           time.perf_counter()))
    driver = cell.driver.Driver(cell, run)
    try:
        driver.setup()
        setup_s = time.perf_counter() - T_START
        before = run.compiles
        run.window = driver.run_for(seconds)
        run.window_compiles = run.compiles - before
        if args.trace:
            _traced_segment(run, driver)
        out = driver.report(run.window)
    finally:
        driver.close()
    run.end_to_end = dict(out["end_to_end"], setup_s=setup_s)
    correct = bool(out["correct"]) and run.window_compiles == 0
    compared = [*driver.compared, ("failed", out["failed"], 0),
                ("window_compiles", run.window_compiles, 0)]

    for note in driver.notes:
        print(f"[perf] {note}", flush=True)
    parts: dict[str, float] = {}
    for name, t0, t1 in run.spans.rows:
        if name.startswith("perf.setup."):
            parts[name[11:]] = parts.get(name[11:], 0.0) + t1 - t0
    print("[perf] set-up by part (s): " + ", ".join(
        f"{k} {v:.2f}" for k, v in parts.items())
        + f", the rest "
        f"{setup_s - sum(parts.values()):.2f}", flush=True)
    print(f"[perf] cell={cell.name} seed={args.seed} window="
          f"{run.window['t1'] - run.window['t0']:.3f}s setup_s={setup_s:.3f} "
          f"compiles in window={run.window_compiles} end_to_end="
          f"{json.dumps(run.end_to_end)}", flush=True)

    metrics = {}
    if args.trace:
        for m in cell.metrics("per_layer"):
            value = cell.reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        for m in cell.metrics("end_to_end"):
            if m["name"] not in run.end_to_end:
                fail(f"driver reported no {m['name']} for {cell.name}", 1)
            metrics[m["name"]] = {"value": float(run.end_to_end[m["name"]]),
                                  "unit": m["unit"]}

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in run.devices)
    device = {"platform": devices[0].platform, "kind": run.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    line = {"correct": correct, "attempted": int(out["attempted"]),
            "failed": int(out["failed"]), "metrics": metrics,
            "device": device}
    if args.trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        line["breakdown"] = {"device_ops": run.trace["device_ops"],
                             "idle_gaps": run.trace["idle_gaps"]}
    line["compared"] = {}
    for name, value, limit in compared:
        print(f"[perf] compared {name}: {value!r} (limit {limit!r})",
              file=sys.stderr, flush=True)
        line["compared"][name] = {
            "value": value if math.isfinite(value) else repr(value),
            "limit": limit}
    print(json.dumps(line), flush=True)
    return 0


def _traced_segment(run: Run, driver) -> None:
    """A few more seconds of the same loop under the profiler: the
    measured window stays free of the profiler's start and stop."""
    import shutil

    import jax

    from perf.harness import trace

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    os.makedirs(TRACE_DIR, exist_ok=True)
    jax.profiler.start_trace(TRACE_DIR,
                             profiler_options=trace.profiler_options())
    run.spans.annotate = True
    try:
        with run.spans.span(trace.WINDOW_SPAN):
            run.traced = driver.run_for(TRACE_SECONDS)
    finally:
        run.spans.annotate = False
        jax.profiler.stop_trace()
    try:
        path = trace.newest_xplane(TRACE_DIR)
        if path is not None:
            run.trace_form = trace.extract(path)
            run.trace = trace.reduce(run.trace_form)
    finally:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
