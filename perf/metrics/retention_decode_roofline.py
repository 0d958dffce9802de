"""Share of its roofline the power-retention decode kernel reaches: the
least time an engine step's ``retention_decode`` calls can take on this
chip over ``kernel_ms.retention_decode``.  The least time is the family's
``retention_decode_least_ms`` of what the engine counted in the TRACED
segment, the same ``run_for`` call the kernel time comes from
(``run.traced["engine_stats"]``: ``retention_state_reads`` and
``retention_tail_rows`` over its ``steps``; the kernel time is over those
steps and the ones that drain the last first tokens, each side over its
own count): every decoding slot-layer's state and normaliser and the tail
rows it attends read once, or their products at the MXU's peak if that is
larger (perf/harness/peaks.json).  Nothing without a trace, the counters
(the parent of the PR that brought them) or the family's function."""

from perf.harness.layers import kernel_ms
from perf.harness.peaks import peaks


def read(run):
    least = getattr(run.cell.family, "retention_decode_least_ms", None)
    took = kernel_ms(run, "retention_decode", "steps_with_drain")
    st = (run.traced or {}).get("engine_stats") or {}
    if least is None or not took or not st.get("steps") or not all(
            k in st for k in ("retention_state_reads",
                              "retention_tail_rows")):
        return None
    return 100.0 * least(run.cell.config,
                         st["retention_state_reads"] / st["steps"],
                         st["retention_tail_rows"] / st["steps"],
                         peaks(run.device_kind)) / took
