"""``tpudp.obs`` — structured telemetry for both runtimes.

One subsystem, four layers (docs/OBSERVABILITY.md):

  * **Spans & events** (:mod:`tpudp.obs.record`): a preallocated
    monotonic-clock ring per engine/trainer.  ``begin``/``end`` is the
    allocation-free hot-path API (the only one the ``obs-in-hot-path``
    lint rule allows on the designated scheduler/step hot paths);
    ``span``/``event`` are the convenient off-hot-path forms.  Closed
    spans also add to per-name cumulative totals that never lap, and
    under a JAX profiler session each is a ``tpudp.<recorder>.<span>``
    annotation on the trace's clock.
  * **Zero-sync device counters**: values accumulated INSIDE the
    existing step programs and carried in the arrays the runtime
    already fetches (the trainer's ``obs_norms`` / ``obs_moe``; the
    serving expert layers' ``moe_*`` counts, which ride the token
    fetch) — never a fetch of their own on a hot path, so
    ``tpudp.analysis lint`` stays at zero host-sync findings.
  * **Flight recorder** (:mod:`tpudp.obs.flight`): the ring persists as
    a per-host ``flightrec-*.json`` on watchdog timeouts, step-failure
    containment, and resilience rollbacks — enable by directory
    (``TPUDP_FLIGHT_DIR`` or the ``flight_dir`` knobs).
  * **Exposition** (:mod:`tpudp.obs.export` / :mod:`tpudp.obs.metrics`):
    Chrome/Perfetto ``trace_event`` JSON and a Prometheus-style text
    endpoint (``tpudp.cli --metrics-port``).

This package also holds the XLA :func:`trace` capture wrapper (ex
``tpudp.utils.profiler.trace``, which re-exports it) and the
reference-parity window-line formatter
(:func:`reference_window_lines`) the Trainer prints through.
Importing ``tpudp.obs`` never imports jax.
"""

from tpudp.obs.export import spans_from_chrome_trace, to_chrome_trace
from tpudp.obs.flight import (FLIGHT_DIR_ENV, FlightRecorder,
                              coordinated_merge, merge_dumps,
                              resolve_flight_dir)
from tpudp.obs.format import reference_window_lines
from tpudp.obs.metrics import MetricsServer, prometheus_text
from tpudp.obs.record import NO_SPAN, Recorder
from tpudp.obs.tracing import trace

__all__ = [
    "FLIGHT_DIR_ENV", "FlightRecorder", "MetricsServer", "NO_SPAN",
    "Recorder", "coordinated_merge", "merge_dumps", "prometheus_text",
    "reference_window_lines", "resolve_flight_dir",
    "spans_from_chrome_trace", "to_chrome_trace", "trace",
]
