"""Share of its roofline the ``moe_gmm`` kernel reaches in a serving cell:
the least time an engine step's grouped products can take on this chip
over ``kernel_ms.serve_moe_gmm``.  The least time is the family's
``serve_gmm_least_ms`` of what the engine counted in the TRACED segment,
the same ``run_for`` call the kernel time comes from
(``run.traced["engine_stats"]``: ``moe_experts_touched`` and
``moe_rows_held`` over its ``steps``; the kernel time is over those steps
and the ones that drain the last first tokens, each side over its own
count): every touched expert's three matrices read once, or the held rows'
three products at the MXU's peak if that is larger
(perf/harness/peaks.json).  Nothing without a trace, the counters (the
parent of the PR that brought them) or the family's function."""

from perf.harness.layers import kernel_ms
from perf.harness.peaks import peaks


def read(run):
    least = getattr(run.cell.family, "serve_gmm_least_ms", None)
    took = kernel_ms(run, "moe_gmm", "steps_with_drain")
    st = (run.traced or {}).get("engine_stats") or {}
    if least is None or not took or not st.get("steps") or not all(
            k in st for k in ("moe_experts_touched", "moe_rows_held")):
        return None
    return 100.0 * least(run.cell.config,
                         st["moe_experts_touched"] / st["steps"],
                         st["moe_rows_held"] / st["steps"],
                         peaks(run.device_kind)) / took
